"""Observability subsystem tests (ISSUE 1): exposition format, slowlog
RPC round-trip with request-id correlation, FPR-drift gauge sanity,
phase breakdown, and the O(1) histogram rewrite."""

import urllib.request

import numpy as np
import pytest

from tpubloom import checkpoint as ckpt
from tpubloom.obs import counters as obs_counters
from tpubloom.obs.context import phase, request
from tpubloom.obs.exposition import parse_families, render_service
from tpubloom.obs.httpd import start_metrics_server
from tpubloom.obs.slowlog import Slowlog, summarize_request
from tpubloom.server.client import BloomClient
from tpubloom.server.metrics import LatencyHistogram
from tpubloom.server.service import BloomService, build_server


@pytest.fixture()
def server(tmp_path):
    service = BloomService(sink_factory=lambda config: ckpt.FileSink(str(tmp_path)))
    srv, port = build_server(service, "127.0.0.1:0")
    srv.start()
    client = BloomClient(f"127.0.0.1:{port}")
    client.wait_ready()
    yield client, service
    client.close()
    srv.stop(grace=None)


# -- LatencyHistogram (satellite: O(1) observe + cumulative buckets) ---------


def test_histogram_bucket_lookup_matches_linear_scan():
    """bit_length indexing must agree with the old linear scan on every
    boundary: us in [2^(i-1), 2^i) -> bucket i, overflow -> last."""
    def linear_bucket(us):
        for i, b in enumerate(LatencyHistogram.BUCKETS):
            if us < b:
                return i
        return len(LatencyHistogram.BUCKETS)

    h = LatencyHistogram()
    probes_us = [0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 1023.0, 1024.0, 1025.0]
    probes_us += [float(2**i) for i in range(28)]
    probes_us += [float(2**i - 1) for i in range(1, 28)]
    for us in probes_us:
        h2 = LatencyHistogram()
        h2.observe(us / 1e6)
        # compare at the value observe() actually sees (the /1e6 * 1e6
        # round-trip may land an ulp off the probe)
        assert h2.counts[linear_bucket((us / 1e6) * 1e6)] == 1, (
            f"bucket drift at {us}us"
        )
        h.observe(us / 1e6)
    assert h.n == len(probes_us)
    cum = h.cumulative()
    assert cum[-1] == h.n
    assert all(b >= a for a, b in zip(cum, cum[1:])), "cumulative must be monotone"
    s = h.summary()
    assert s["n"] == h.n and "p50_us_lt" in s and "p99_us_lt" in s
    assert s["buckets_cum"] == cum


# -- slowlog core ------------------------------------------------------------


def test_slowlog_keeps_slowest_and_resets():
    sl = Slowlog(capacity=3)
    for i, d in enumerate([0.01, 0.5, 0.02, 0.9, 0.03, 0.001]):
        sl.record(method="M", duration_s=d, rid=f"r{i}", batch=i)
    got = [e["duration_s"] for e in sl.entries()]
    assert got == [0.9, 0.5, 0.03], "must keep the slowest, slowest-first"
    assert sl.entries(2) == sl.entries()[:2]
    assert sl.total_recorded == 6
    assert sl.reset() == 3 and len(sl) == 0
    sl.record(method="M", duration_s=1.0)
    assert len(sl) == 1  # records again after reset


def test_summarize_request_redacts_keys():
    s = summarize_request("InsertBatch", {"name": "urls", "keys": [b"a"] * 7,
                                          "rid": "deadbeef"})
    assert "keys[7]" in s and "deadbeef" not in s and "urls" in s


# -- exposition format -------------------------------------------------------


def test_exposition_golden_scrape_and_monotone_counters(server):
    client, service = server
    client.create_filter("expo", capacity=10_000, error_rate=0.01)
    client.insert_batch("expo", [b"k%d" % i for i in range(500)])
    client.include_batch("expo", [b"k1", b"nope"])

    text = render_service(service)
    fam = parse_families(text)
    for name in (
        "tpubloom_uptime_seconds",
        "tpubloom_keys_inserted_total",
        "tpubloom_keys_queried_total",
        "tpubloom_rpc_duration_seconds_bucket",
        "tpubloom_rpc_duration_seconds_count",
        "tpubloom_rpc_phase_seconds_bucket",
        "tpubloom_filter_fill_ratio",
        "tpubloom_filter_bits_set",
        "tpubloom_filter_estimated_fpr",
        "tpubloom_filter_predicted_fpr",
        "tpubloom_filter_fpr_drift",
        "tpubloom_slowlog_entries",
    ):
        assert name in fam, f"scrape must contain {name}"
    assert fam["tpubloom_keys_inserted_total"][()] == 500

    # histogram sanity: bucket series is cumulative and ends at _count
    buckets = {
        k: v
        for k, v in fam["tpubloom_rpc_duration_seconds_bucket"].items()
        if dict(k)["method"] == "InsertBatch"
    }
    series = [v for k, v in sorted(
        buckets.items(),
        key=lambda kv: float(dict(kv[0])["le"].replace("+Inf", "inf")),
    )]
    assert all(b >= a for a, b in zip(series, series[1:]))
    assert series[-1] == fam["tpubloom_rpc_duration_seconds_count"][
        (("method", "InsertBatch"),)
    ]

    # counters are monotone across scrapes
    client.insert_batch("expo", [b"more-%d" % i for i in range(100)])
    fam2 = parse_families(render_service(service))
    assert fam2["tpubloom_keys_inserted_total"][()] == 600
    assert (
        fam2["tpubloom_rpc_duration_seconds_count"][(("method", "InsertBatch"),)]
        > fam["tpubloom_rpc_duration_seconds_count"][(("method", "InsertBatch"),)]
    )


def test_metrics_http_endpoint(server):
    client, service = server
    client.create_filter("http", capacity=1000, error_rate=0.01)
    client.insert_batch("http", [b"a", b"b"])
    ms = start_metrics_server(service, port=0, host="127.0.0.1")
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ms.port}/metrics", timeout=10
        ) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            fam = parse_families(resp.read().decode())
        assert fam["tpubloom_keys_inserted_total"][()] == 2
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ms.port}/healthz", timeout=10
        ) as resp:
            assert resp.status == 200
    finally:
        ms.close()


# -- slowlog RPC round-trip + request-id correlation -------------------------


def test_slowlog_rpc_roundtrip_with_rids(server):
    client, _ = server
    client.create_filter("slow", capacity=10_000, error_rate=0.01)
    rids = {}
    client.insert_batch("slow", [b"s%d" % i for i in range(256)])
    rids["InsertBatch"] = client.last_rid
    client.include_batch("slow", [b"s0", b"s1"])
    rids["QueryBatch"] = client.last_rid

    entries = client.slowlog_get()
    assert entries, "traffic must populate the slowlog"
    assert entries == sorted(entries, key=lambda e: -e["duration_s"])
    by_rid = {e["rid"]: e for e in entries}
    for method, rid in rids.items():
        assert rid in by_rid, f"{method} rid must round-trip into the slowlog"
        e = by_rid[rid]
        assert e["method"] == method
        assert e["batch"] == (256 if method == "InsertBatch" else 2)
        assert e["duration_s"] > 0 and "keys[" in e["args"]
        # phase breakdown rides along (decode is wire-level, kernel /
        # kernel_query is the device pass the filter layer recorded —
        # the read path gets its own span since ISSUE 12)
        kphase = "kernel" if method == "InsertBatch" else "kernel_query"
        assert {"decode", "host_prep", kphase, "encode"} <= set(e["phases"])
        assert sum(e["phases"].values()) <= e["duration_s"] + 1e-6

    n_before = len(client.slowlog_get())
    # >=: the SlowlogGet call above records ITSELF once it finishes
    assert client.slowlog_reset() >= n_before
    # only the reset/get RPCs themselves can be in the log afterwards
    assert {e["method"] for e in client.slowlog_get()} <= {
        "SlowlogGet", "SlowlogReset"
    }


def test_slowlog_get_n_limits(server):
    client, _ = server
    client.create_filter("lim", capacity=1000, error_rate=0.01)
    for i in range(5):
        client.insert_batch("lim", [b"x%d" % i])
    assert len(client.slowlog_get(3)) == 3


# -- gauges ------------------------------------------------------------------


def test_fpr_drift_gauge_sanity():
    """After N random inserts the observed (fill-derived) FPR must sit
    close to the analytic prediction — the drift gauge reads ~0 for an
    honest filter and random keys."""
    from tpubloom import BloomFilter, FilterConfig

    cfg = FilterConfig(m=1 << 18, k=4, key_len=16)
    f = BloomFilter(cfg)
    rng = np.random.default_rng(7)
    f.insert_batch([rng.bytes(16) for _ in range(20_000)])
    st = f.stats()
    assert 0 < st["predicted_fpr"] < 1 and 0 < st["estimated_fpr"] < 1
    assert st["estimated_fpr"] == pytest.approx(st["predicted_fpr"], rel=0.15)
    assert st["fpr_drift"] == pytest.approx(
        st["estimated_fpr"] - st["predicted_fpr"]
    )
    assert st["bits_set"] == pytest.approx(st["fill_ratio"] * cfg.m, abs=1.0)
    # duplicate inserts violate the distinct-keys sizing assumption ->
    # the drift gauge must go measurably negative (observed < predicted)
    f.insert_batch([b"dup-key"] * 4096)
    st2 = f.stats()
    assert st2["fpr_drift"] < st["fpr_drift"]


def test_sharded_per_shard_fill_gauges():
    from tpubloom import FilterConfig
    from tpubloom.parallel.sharded import ShardedBloomFilter

    cfg = FilterConfig(m=1 << 20, k=4, key_len=16, shards=8, key_name="shobs")
    f = ShardedBloomFilter(cfg)
    rng = np.random.default_rng(3)
    f.insert_batch([rng.bytes(16) for _ in range(4000)])
    fills = f.shard_fill_ratios()
    assert len(fills) == 8 and all(fl > 0 for fl in fills)
    # routing spreads uniformly: no shard way off the mean
    assert max(fills) < 3 * min(fills)
    st = f.stats()
    assert st["fill_ratio_per_shard"] == pytest.approx(fills, rel=0.01)
    assert st["fill_ratio"] == pytest.approx(float(np.mean(fills)), rel=0.05)


def test_checkpoint_gauges(tmp_path):
    from tpubloom import BloomFilter, FilterConfig

    sink = ckpt.FileSink(str(tmp_path))
    f = BloomFilter(FilterConfig(m=1 << 16, k=4, key_name="ckobs"))
    cp = ckpt.AsyncCheckpointer(f, sink, every_n_inserts=100)
    f.insert_batch([b"a", b"b"])
    cp.notify_inserts(2)
    st = cp.obs_stats()
    assert st["lag_inserts"] == 2 and st["checkpoints_written"] == 0
    assert st["age_seconds"] is None
    assert cp.trigger() and cp.flush()
    st = cp.obs_stats()
    assert st["lag_inserts"] == 0, "a manual trigger must reset the lag gauge"
    assert st["checkpoints_written"] == 1
    assert st["age_seconds"] >= 0 and st["last_duration_seconds"] > 0
    assert st["last_error"] is None
    cp.close(final_checkpoint=False)


# -- phase context (unit) ----------------------------------------------------


def test_phase_context_accumulates_and_noops():
    with phase("orphan"):  # no active request: must be a silent no-op
        pass
    with request("TestMethod") as rctx:
        with phase("kernel"):
            pass
        with phase("kernel"):
            pass
        with phase("d2h"):
            pass
    assert set(rctx.phases) == {"kernel", "d2h"}
    assert rctx.rid and len(rctx.rid) == 16


def test_global_counters_roundtrip():
    obs_counters.incr("obs_test_counter", 3)
    assert obs_counters.get("obs_test_counter") == 3
    assert obs_counters.global_counters()["obs_test_counter"] == 3


# -- the tier-1 smoke (satellite: CI/tooling) --------------------------------


def test_obs_smoke():
    """The benchmarks/obs_smoke.py end-to-end check runs in tier-1."""
    import importlib
    import os
    import sys

    from tpubloom.obs import blackbox

    # the black box is process-global: an in-process replica applier
    # from another module run earlier on this worker (test_ha's) leaves
    # it armed, and the smoke measures the disabled default first
    blackbox.reset_for_tests()
    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")
    )
    try:
        obs_smoke = importlib.import_module("obs_smoke")
        result = obs_smoke.run_smoke()
    finally:
        sys.path.pop(0)
    assert result["ok"] and result["slowlog_entries"] > 0
    assert result["insert_rid_correlated"]
