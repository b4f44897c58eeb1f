"""Cross-layer observability subsystem.

Parity: the reference gem ships no metrics of its own — operators lean on
Redis ``INFO`` / ``SLOWLOG`` / ``MONITOR`` (SURVEY.md §5). This package is
the TPU-native replacement for that operator surface, pinned by BASELINE's
observability row: keys inserted/queried, batch sizes, kernel/request
latency, checkpoint lag, fill ratio & predicted FPR — all scrapeable,
without attaching a profiler or running bench archaeology.

Pieces (each importable on its own, stdlib-only except where noted):

* :mod:`tpubloom.obs.context` — thread-local request context + named
  phase timers (decode / host_prep / h2d / kernel / d2h / encode, and
  the coalescer's flush steps). The filter layer records phases into
  whatever request is active; with no active request every span is a
  no-op, so library users pay ~nothing. Under a context each phase is
  also a ``tpubloom.<phase>`` ``jax.profiler`` host span (the one
  non-stdlib import here).
* :mod:`tpubloom.obs.counters` — process-global counters for events that
  happen below the server layer (e.g. ``geometry_probe_demotions`` when a
  Pallas geometry probe demotes to scatter), merged into ``/metrics``.
* :mod:`tpubloom.obs.slowlog` — Redis-SLOWLOG-parity ring of the N
  slowest requests (method, args summary, batch, duration, request id,
  phase breakdown), served by the ``SlowlogGet``/``SlowlogReset`` RPCs.
* :mod:`tpubloom.obs.exposition` — Prometheus text-format rendering of
  the server's counters, latency/phase histograms, per-filter and
  checkpoint gauges, and the global counters.
* :mod:`tpubloom.obs.httpd` — the background HTTP thread serving
  ``GET /metrics`` (plus ``/healthz``, ``/trace?rid=`` and
  ``/flight``), enabled by the server's ``--metrics-port`` flag.
* :mod:`tpubloom.obs.trace` — distributed request tracing (ISSUE 15):
  a Dapper-style span ring keyed on the client rid, behind the
  server's ``--trace-sample`` knob, served by the ``TraceGet`` RPC.
* :mod:`tpubloom.obs.flight` — the flight recorder (ISSUE 15): a
  bounded lock-free ring of lifecycle events dumped to JSON on
  SIGTERM / fatal / Health-DEGRADED flips and on demand.

Request correlation: the gRPC client stamps every request with a ``rid``
(``BloomClient.last_rid``); the server threads it into
``tracing.annotate`` spans AND the slowlog entry, so a slow request found
in SLOWLOG can be looked up by id in a Perfetto trace of the same window
— on the direct path. A coalesced request's work runs in a flush that
serves many rids; its steps appear as ``tpubloom.<phase>`` spans on the
dispatcher thread and in the ``ingest_flush_phase_seconds`` histograms.
"""

from tpubloom.obs.context import (  # noqa: F401
    RequestContext,
    current,
    current_rid,
    fencing,
    new_rid,
    phase,
    request,
)
from tpubloom.obs.counters import global_counters, incr  # noqa: F401
from tpubloom.obs.slowlog import Slowlog, summarize_request  # noqa: F401
