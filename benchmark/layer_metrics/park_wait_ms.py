"""Mean time a coalesced request waited parked in the coalescer's queue,
from its park to the pop of its group for a flush, over the measured
window (server and coalescer layer): ``ingest_park_wait_seconds`` sum
over count."""


def read(ctx):
    n = ctx.delta("tpubloom_ingest_park_wait_seconds_count")
    return ctx.delta("tpubloom_ingest_park_wait_seconds_sum") / n * 1e3 if n > 0 else None
