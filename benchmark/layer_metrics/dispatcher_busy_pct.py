"""Share of the measured window in which the coalescer's dispatcher
thread was not waiting for work (server and coalescer layer):
100 x (1 - ``ingest_dispatch_idle_seconds`` sum / window), the window
being the increase of ``tpubloom_uptime_seconds``. A program that does
not file the idle waits has nothing to read."""

IDLE = "tpubloom_ingest_dispatch_idle_seconds_sum"


def read(ctx):
    window = ctx.delta("tpubloom_uptime_seconds")
    if IDLE not in ctx.scrape1 or window <= 0:
        return None
    return 100.0 * (1.0 - ctx.delta(IDLE) / window)
