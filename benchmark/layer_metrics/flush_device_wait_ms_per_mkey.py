"""Dispatcher wall time per million coalesced keys spent on the device
side of a flush (filter front end): the ``lock_wait``, ``h2d``,
``kernel``, ``kernel_query``, ``d2h`` and ``settle`` phases of
``ingest_flush_phase_seconds`` over ``ingest_keys_coalesced``, across the
measured window. Flush launches are not fenced inside ``kernel``, so the
wait for the device lands in ``d2h`` or ``settle``; the sum holds it."""

PHASES = ("lock_wait", "h2d", "kernel", "kernel_query", "d2h", "settle")


def read(ctx):
    s = sum(ctx.delta("tpubloom_ingest_flush_phase_seconds_sum", phase=p) for p in PHASES)
    keys = ctx.delta("tpubloom_ingest_keys_coalesced_total")
    return s / keys * 1e9 if s > 0 and keys > 0 else None
