"""Thread-local request context + phase timers.

The server opens a :func:`request` around every RPC, and the ingest
coalescer opens one around every flush (plus one for its dispatcher
loop); lower layers (``filter.py`` packing/dispatch, protocol
decode/encode, the flush's own steps) wrap their work in :func:`phase`
spans. Phases accumulate on the innermost active context; with no
context active a span is a no-op ``yield``, so the library hot path
outside the server pays one truthy check per span. Under a context each
phase is also a ``jax.profiler`` host span named ``tpubloom.<phase>``
(the context's method and key count as metadata), so a profiler trace
shows the phase on the same clock as the device ops.

Phase vocabulary (DECLARED in :data:`tpubloom.obs.names.PHASES` /
:data:`tpubloom.obs.names.PHASE_DYNAMIC_PREFIXES` — the lint's
``phase-registry`` check closes both directions, so a name used here
but not declared there, or declared but never emitted, fails CI; the
semantics stay documented in this module):

* ``decode``    — wire bytes -> request dict (msgpack)
* ``host_prep`` — key packing + batch padding on the host
* ``h2d``       — staging packed arrays onto the device
* ``kernel``    — jitted MUTATING device work (dispatch + completion
  fence): inserts, deletes, fused test-and-insert
* ``kernel_query`` — jitted READ-ONLY device work (membership queries)
  — split from ``kernel`` since ISSUE 12 so the read path's device time
  is trackable on its own (the query sweep kernel is the direct lever
  on it)
* ``d2h``       — device results -> host arrays
* ``encode``    — response dict -> wire bytes

The coalescer's flush steps, on its dispatcher thread
(:mod:`tpubloom.server.ingest`; leaves, none overlaps another):

* ``merge``     — concatenating the parked requests' keys (and demoting
  rows wider than the filter's key_len to a key list)
* ``unpack``    — turning merged fixed-width rows back into a key list
  for a launch that takes one
* ``lock_wait`` — waiting to take the filter's op lock
* ``settle``    — fencing the previous double-buffered insert launch
* ``log``       — the flush's op-log append and checkpoint notify
* ``demux``     — per-request verdict slices, ``packbits`` and completion
* ``dispatch_idle`` — the dispatcher waiting for work, nothing ripe

Sharded filters additionally emit ``kernel_shard<i>`` spans on the
direct (per-request) path: per-device time-to-completion of one mesh
launch, measured from the fence start (ROADMAP 1(c) — the straggler
shard is the widest span).

Under JAX async dispatch the h2d/kernel boundary is approximate (the
transfer may still be in flight when dispatch starts); the completion
fence inside ``kernel`` makes the SUM honest, which is what the
transport-bound vs code-bound triage needs. A background context (the
coalescer's) does not fence (:func:`fencing`): there the wait for the
device lands in ``d2h`` or ``settle`` instead.
"""

from __future__ import annotations

import contextlib
import random
import threading
import time
from typing import Iterator, Optional

from tpubloom.utils.tracing import host_span

_tls = threading.local()

#: Set by :func:`tpubloom.obs.trace.configure` (ISSUE 15): when the
#: trace ring is armed, fresh request contexts carry an event buffer so
#: phase timers double as child spans; disarmed (the default) they
#: carry None and the hot path pays one falsy check per phase.
_trace_capture = False


def set_trace_capture(on: bool) -> None:
    global _trace_capture
    _trace_capture = bool(on)


def new_rid() -> str:
    """16-hex-char request id; cheap, collision-safe at slowlog scale."""
    return "%016x" % random.getrandbits(64)


class RequestContext:
    """Per-request accumulator: id, batch size, phase durations — plus,
    with tracing armed, the buffered child-span events and the capture
    decision :mod:`tpubloom.obs.trace` commits at finish."""

    __slots__ = (
        "method", "rid", "batch", "summary", "phases", "phases_cpu",
        "fence", "started_at", "trace_events", "trace_armed",
        "trace_span", "trace_parent", "trace_forced",
    )

    def __init__(
        self,
        method: str,
        rid: Optional[str] = None,
        *,
        background: bool = False,
        thread_cpu: bool = False,
    ):
        self.method = method
        #: a background context belongs to no request: it carries no rid
        #: of its own (what it logs is attributed to none) unless given one
        self.rid = rid if background else (rid or new_rid())
        self.batch = 0
        self.summary = ""
        self.phases: dict[str, float] = {}
        #: phase -> this thread's CPU seconds (``time.thread_time``),
        #: kept only when asked for; wall minus CPU is time blocked
        self.phases_cpu: Optional[dict] = {} if thread_cpu else None
        #: whether the filter layer fences its launches (:func:`fencing`)
        self.fence = not background
        self.started_at = time.time()
        #: (name, wall start, duration, attrs, is_phase) child events,
        #: or None when tracing is off (zero per-phase overhead) or the
        #: context is a background one (never committed as a tree)
        self.trace_events: Optional[list] = (
            [] if _trace_capture and not background else None
        )
        self.trace_armed = False
        self.trace_span: Optional[str] = None
        self.trace_parent: Optional[str] = None
        #: the wire trace field forced capture (ISSUE 16: forced
        #: requests spill their tree to the crash-forensics black box)
        self.trace_forced = False

    def add_phase(self, name: str, seconds: float) -> None:
        # += : a phase may run more than once per request (e.g. kernel
        # twice for the query-then-insert presence fallback)
        self.phases[name] = self.phases.get(name, 0.0) + seconds


def current() -> Optional[RequestContext]:
    return getattr(_tls, "ctx", None)


def current_rid() -> Optional[str]:
    ctx = current()
    return ctx.rid if ctx is not None else None


def fencing() -> bool:
    """Whether the filter layer should fence a launch inside its
    ``kernel``/``kernel_query`` phase, so the phase times device work
    rather than async dispatch: yes under a request's context; no with
    no context (the library path keeps JAX's async pipelining) or under
    a background one (the coalescer's launches stay as unfenced as they
    run untimed — double buffering depends on it)."""
    ctx = current()
    return ctx is not None and ctx.fence


@contextlib.contextmanager
def request(
    method: str,
    rid: Optional[str] = None,
    *,
    background: bool = False,
    thread_cpu: bool = False,
) -> Iterator[RequestContext]:
    """Install a fresh RequestContext for this thread (re-entrant: the
    previous context is restored on exit, so nested server calls don't
    cross-contaminate phases). ``background=True`` opens one for work
    done on behalf of no single request (the coalescer's flushes and
    dispatcher loop): phases are timed, but no rid is minted and no
    launch is fenced. ``thread_cpu=True`` also keeps each phase's
    thread CPU time in ``phases_cpu``."""
    ctx = RequestContext(
        method, rid, background=background, thread_cpu=thread_cpu
    )
    prev = current()
    _tls.ctx = ctx
    try:
        yield ctx
    finally:
        _tls.ctx = prev


@contextlib.contextmanager
def phase(name: str) -> Iterator[None]:
    """Time a named phase into the active request context, inside a
    ``tpubloom.<name>`` profiler host span (no-op without a context)."""
    ctx = current()
    if ctx is None:
        yield
        return
    events = ctx.trace_events
    cpu = ctx.phases_cpu
    w0 = time.time() if events is not None else 0.0
    with host_span("tpubloom." + name, op=ctx.method, keys=ctx.batch):
        # the wall interval encloses the CPU one; where the thread
        # clock advances in scheduler ticks, one phase's CPU time is
        # a sample and only sums over many phases are meaningful
        t0 = time.perf_counter()
        c0 = time.thread_time() if cpu is not None else 0.0
        try:
            yield
        finally:
            c1 = time.thread_time() if cpu is not None else 0.0
            dt = time.perf_counter() - t0
            ctx.add_phase(name, dt)
            if cpu is not None:
                cpu[name] = cpu.get(name, 0.0) + c1 - c0
            if events is not None:
                # the phase timer doubles as a child span — committed
                # as phase.<name> under the request's root span when
                # the request is captured (trace.commit_children)
                events.append((name, w0, dt, None, True))
