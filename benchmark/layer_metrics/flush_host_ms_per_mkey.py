"""Dispatcher wall time per million coalesced keys in the flush's host
steps (server and coalescer layer): the ``merge``, ``unpack``,
``host_prep``, ``log`` and ``demux`` phases of
``ingest_flush_phase_seconds`` over ``ingest_keys_coalesced``, across the
measured window. A program without those histograms has nothing to read."""

PHASES = ("merge", "unpack", "host_prep", "log", "demux")


def read(ctx):
    s = sum(ctx.delta("tpubloom_ingest_flush_phase_seconds_sum", phase=p) for p in PHASES)
    keys = ctx.delta("tpubloom_ingest_keys_coalesced_total")
    return s / keys * 1e9 if s > 0 and keys > 0 else None
