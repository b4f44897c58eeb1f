"""Share of the flush's host steps (``merge``, ``unpack``, ``host_prep``,
``log``, ``demux``) in which the dispatcher thread was off its CPU —
waiting for the GIL, a lock or the device — over the measured window
(server and coalescer layer): 100 x (1 - thread CPU seconds /
wall seconds) of those phases, from ``ingest_flush_phase_cpu_seconds``
and ``ingest_flush_phase_seconds``."""

PHASES = ("merge", "unpack", "host_prep", "log", "demux")


def read(ctx):
    wall = sum(ctx.delta("tpubloom_ingest_flush_phase_seconds_sum", phase=p) for p in PHASES)
    cpu = sum(ctx.delta("tpubloom_ingest_flush_phase_cpu_seconds_sum", phase=p) for p in PHASES)
    return 100.0 * (1.0 - cpu / wall) if wall > 0 else None
