"""Kernel tracing/profiling helpers — the SURVEY.md §5 "Tracing/profiling"
subsystem.

Parity: the reference gem has no tracing; operators use Redis
SLOWLOG/MONITOR. The TPU-native equivalent pinned by SURVEY.md §5 is
``jax.profiler`` traces (viewable in Perfetto / XProf / TensorBoard)
around the insert/query kernels, plus named annotations so individual
batches show up in the trace timeline.

Usage::

    from tpubloom.utils import tracing

    with tracing.trace("/tmp/tpubloom-trace"):     # whole-session trace
        with tracing.annotate("insert_batch", batch=len(keys)):
            f.insert_batch(keys)

    # or one-shot around a callable:
    result, trace_dir = tracing.profile_call(fn, *args)

The gRPC server wires ``request_span`` around every request it serves on
the direct (uncoalesced) path, so those spans appear in device traces
(tpubloom/server/service.py) carrying the client-generated request id —
the same id the slowlog entry records (``tpubloom.obs.slowlog``), so
"find slowlog entry rid=X, open the trace, search rid=X" is the triage
loop. Coalesced requests have no such span: their work runs in a flush
on the coalescer's dispatcher thread, whose steps appear as the
``tpubloom.<phase>`` spans :func:`tpubloom.obs.context.phase` enters.
"""

from __future__ import annotations

import contextlib
import os
import tempfile
from typing import Any, Callable, Iterator

import jax


@contextlib.contextmanager
def trace(log_dir: str, *, create: bool = True) -> Iterator[str]:
    """Capture a jax.profiler device+host trace into ``log_dir``.

    The resulting ``plugins/profile/**/*.trace.json.gz`` /
    ``*.xplane.pb`` files open in Perfetto (ui.perfetto.dev) or
    TensorBoard's profile plugin.
    """
    if create:
        os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


def host_span(name: str, **meta: Any) -> jax.profiler.TraceAnnotation:
    """A host span in the profiler timeline: a TraceMe named ``name``
    with ``meta`` as keyword metadata (stats on the event, not part of
    its name). Entering it costs well under a microsecond while no
    profiler trace is running. The one helper behind :func:`annotate`
    and the ``obs.context`` phase timers."""
    return jax.profiler.TraceAnnotation(name, **meta)


@contextlib.contextmanager
def annotate(name: str, **attrs: Any) -> Iterator[None]:
    """Named span in the profiler timeline (:func:`host_span`).

    ``attrs`` are folded into the span name, so that a timeline search
    for ``rid=<id>`` finds the span — keep them short, e.g.
    ``batch=4096``.
    """
    if attrs:
        name = name + "[" + ",".join(f"{k}={v}" for k, v in attrs.items()) + "]"
    with host_span(name):
        yield


def request_span(
    name: str, *, batch: int | None = None, rid: str | None = None
) -> Iterator[None]:
    """Request-correlated :func:`annotate` span: folds the batch size and
    request id into the span name, silently dropping absent attrs (a
    library call without an active RPC has no rid)."""
    attrs: dict[str, Any] = {}
    if batch is not None:
        attrs["batch"] = batch
    if rid:
        attrs["rid"] = rid
    return annotate(name, **attrs)


def profile_call(
    fn: Callable[..., Any], *args: Any, log_dir: str | None = None, **kwargs: Any
) -> tuple[Any, str]:
    """Run ``fn(*args, **kwargs)`` under a one-shot trace.

    Returns ``(result, trace_dir)``. Blocks on the result so device work
    lands inside the captured window.
    """
    if log_dir is None:
        log_dir = tempfile.mkdtemp(prefix="tpubloom-trace-")
    with trace(log_dir):
        result = fn(*args, **kwargs)
        jax.block_until_ready(result)
    return result, log_dir
