"""The ingest coalescer's dispatcher, instrumented: every flush step is a
phase of a per-flush context, filed once per flush as wall and
dispatcher-CPU histograms and entered as a ``tpubloom.<phase>`` profiler
span — without fencing the double-buffered launch earlier or stamping
a rid on an untraced flush's op-log record."""

import glob
import os
import threading
import time

import numpy as np
import pytest

from tpubloom.obs.exposition import parse_families, render_service
from tpubloom.server.ingest import CoalesceConfig
from tpubloom.server.service import BloomService

FILTER = {"m": 1 << 20, "k": 7, "key_len": 16, "block_bits": 512}
KEYS_PER_REQUEST = 64

INSERT_PRESENCE_PHASES = {
    "merge", "unpack", "lock_wait", "host_prep", "h2d", "kernel", "d2h",
    "settle", "log", "demux",
}
QUERY_PHASES = {
    "merge", "host_prep", "h2d", "lock_wait", "kernel_query", "d2h", "demux",
}


def _service(**kw) -> BloomService:
    svc = BloomService(
        coalesce=CoalesceConfig(max_keys=4096, max_wait_us=2000), **kw
    )
    svc.CreateFilter({"name": "f", "config": FILTER})
    return svc


@pytest.fixture()
def svc():
    s = _service()
    yield s
    s.shutdown()


def _req(seed: int, **extra) -> dict:
    rows = np.random.default_rng(seed).integers(
        0, 256, (KEYS_PER_REQUEST, 16), np.uint8
    )
    return {
        "name": "f",
        "rid": "req-%d" % seed,
        "keys_fixed": {"data": rows.tobytes(), "width": 16,
                       "n": KEYS_PER_REQUEST},
        **extra,
    }


def _drive(svc, method: str, seeds, **extra) -> list:
    """Send one request per seed concurrently (so they coalesce) and
    return the responses."""
    out, errs = [None] * len(seeds), []

    def go(i, seed):
        try:
            out[i] = getattr(svc, method)(_req(seed, **extra))
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    ts = [threading.Thread(target=go, args=(i, s)) for i, s in enumerate(seeds)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    if errs:
        raise errs[0]
    # a flush files its phases after completing its requests: wait for
    # the dispatcher to finish the last one
    co, deadline = svc._coalescer, time.monotonic() + 30
    while (co._flushing or co._groups) and time.monotonic() < deadline:
        time.sleep(0.001)
    return out


def _families(svc) -> dict:
    return parse_families(render_service(svc))


def _phase_counts(fam: dict, family: str, op: str) -> dict:
    return {
        dict(labels)["phase"]: n
        for labels, n in fam.get(f"tpubloom_{family}_count", {}).items()
        if dict(labels)["op"] == op
    }


@pytest.mark.parametrize(
    "method,extra,op,counter,phases",
    [
        ("InsertBatch", {"return_presence": True}, "insert",
         "ingest_fused_flushes", INSERT_PRESENCE_PHASES),
        ("QueryBatch", {}, "query", "ingest_query_flushes", QUERY_PHASES),
    ],
    ids=["insert_presence", "query"],
)
def test_each_flush_files_every_phase_once(svc, method, extra, op, counter,
                                           phases):
    for round_ in range(3):
        _drive(svc, method, range(round_ * 8, round_ * 8 + 8), **extra)
    flushes = svc.metrics.counters[counter]
    assert flushes >= 3
    fam = _families(svc)
    for family in ("ingest_flush_phase_seconds",
                   "ingest_flush_phase_cpu_seconds"):
        got = _phase_counts(fam, family, op)
        assert got == {p: flushes for p in phases}, family
    # the wall time of a phase holds its CPU time (thread CPU <= wall)
    wall = fam["tpubloom_ingest_flush_phase_seconds_sum"]
    cpu = fam["tpubloom_ingest_flush_phase_cpu_seconds_sum"]
    assert sum(cpu.values()) <= sum(wall.values()) * 1.05 + 1e-3
    # the request families are untouched: no flush phase reaches them
    rpc = fam.get("tpubloom_rpc_phase_seconds_count", {})
    assert not any(dict(k).get("phase") in phases for k in rpc)


def test_park_wait_one_observation_per_coalesced_request(svc):
    _drive(svc, "InsertBatch", range(10), return_presence=True)
    _drive(svc, "QueryBatch", range(6))
    fam = _families(svc)
    waits = fam["tpubloom_ingest_park_wait_seconds_count"]
    assert waits[(("op", "insert"),)] == 10
    assert waits[(("op", "query"),)] == 6
    assert svc.metrics.counters["ingest_requests_coalesced"] == 16
    sums = fam["tpubloom_ingest_park_wait_seconds_sum"]
    assert all(0 <= v < 60 for v in sums.values())


def test_dispatch_idle_waits_are_filed(svc):
    _drive(svc, "InsertBatch", range(4), return_presence=True)
    _drive(svc, "QueryBatch", range(4))  # ends at least one idle wait
    fam = _families(svc)
    assert fam["tpubloom_ingest_dispatch_idle_seconds_count"][()] >= 1
    assert fam["tpubloom_ingest_dispatch_idle_seconds_sum"][()] > 0


def test_staged_insert_flush_is_not_fenced_before_settle(svc):
    """A plain fixed-width insert flush takes the staged, double-buffered
    launch: nothing fences it until the settle takes it from InFlight.
    An untraced presence flush, whose verdict read is its fence, gets no
    extra completion fence inside its kernel phase either."""
    co = svc._coalescer
    mf = svc._get("f")
    events = []
    lock = threading.Lock()

    def note(what):
        with lock:
            events.append(what)

    orig_fence, orig_bur = mf.filter._kernel_fence, mf.filter.block_until_ready
    orig_put, orig_take = co._inflight.put, co._inflight.take

    def fence(handle):
        note("fence")
        return orig_fence(handle)

    def bur():
        note("fence")
        return orig_bur()

    def put(handle, payload):
        note("put")
        return orig_put(handle, payload)

    def take():
        if co._inflight.pending:
            note("settle")
        return orig_take()

    mf.filter._kernel_fence, mf.filter.block_until_ready = fence, bur
    co._inflight.put, co._inflight.take = put, take
    for round_ in range(3):
        resps = _drive(svc, "InsertBatch", range(round_ * 4, round_ * 4 + 4))
        assert all(r["ok"] for r in resps)
    _drive(svc, "InsertBatch", range(20, 24), return_presence=True)
    assert "put" in events, events  # the staged launch was parked
    assert "fence" not in events, events
    assert events.index("settle") > events.index("put")
    # the idle loop's settle joins the insert flushes' phases
    counts = _phase_counts(_families(svc), "ingest_flush_phase_seconds",
                           "insert")
    assert counts.get("settle", 0) >= svc.metrics.counters["ingest_plain_flushes"]


def test_untraced_flush_op_log_record_carries_no_rid(tmp_path):
    from tpubloom.repl import OpLog

    svc = _service(oplog=OpLog(str(tmp_path / "log")))
    try:
        _drive(svc, "InsertBatch", range(6))
        _drive(svc, "InsertBatch", range(6, 12), return_presence=True)
        records = [r for r in svc.oplog.read_from(0)
                   if r["method"] == "InsertBatch"]
    finally:
        svc.shutdown()
        svc.oplog.close()
    assert records
    assert all(r.get("rid") is None for r in records), records
    # the replay-unsafe (presence) requests still ride as parts with
    # their own rids, as before
    parts = [p for r in records for p in (r["req"].get("parts") or [])]
    assert sorted(rid for rid, _ in parts) == sorted(
        "req-%d" % s for s in range(6, 12)
    )


def test_profiler_trace_names_flush_steps(svc, tmp_path):
    import jax
    from jax.profiler import ProfileData

    _drive(svc, "InsertBatch", range(4), return_presence=True)  # compile
    _drive(svc, "QueryBatch", range(4))
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        _drive(svc, "InsertBatch", range(4, 8), return_presence=True)
        _drive(svc, "QueryBatch", range(4, 8))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    seen: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tpubloom."):
                    seen.setdefault(ev.name, dict(ev.stats))
    for name in ("merge", "host_prep", "d2h", "demux", "dispatch_idle"):
        assert f"tpubloom.{name}" in seen, sorted(seen)
    # the name stays fixed; op and key count ride as metadata
    assert seen["tpubloom.merge"]["op"] in ("ingest.insert", "ingest.query")
    assert seen["tpubloom.merge"]["keys"] >= KEYS_PER_REQUEST
