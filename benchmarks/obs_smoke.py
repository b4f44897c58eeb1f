#!/usr/bin/env python
"""Observability smoke: boot the full server stack on CPU, drive it, and
assert the operator surface is actually there.

What it checks (the ISSUE-1 acceptance list, end to end):

* a real gRPC server + the background metrics HTTP thread come up;
* insert/query batches flow through the wire protocol;
* ``GET /metrics`` parses as Prometheus text format and contains
  ``tpubloom_keys_inserted_total``, per-RPC latency buckets, fill-ratio
  and checkpoint-lag gauges, and the per-phase histogram;
* ``SlowlogGet`` returns entries whose request ids match the ids the
  client generated;
* tracing (ISSUE 15): the sampling-OFF path ships NO wire fields and
  pays no measurable overhead (insert throughput with the ring armed at
  1.0 must stay within a generous factor of the off path — re-measured
  once like the other perf gates), and the sampling-ON path produces a
  span tree (``rpc.InsertBatch`` root + phase children) retrievable by
  rid via ``TraceGet``;
* crash-forensics black box (ISSUE 16): disabled by default (the
  disabled path is the same one-truthy-check note path the phases
  above measure), and with the mmap'd rings armed the write-through +
  slowlog-worthy span spills stay within the same generous overhead
  bound — plus the spilled ring decodes cleanly via ``read_node``.

Run directly (``python benchmarks/obs_smoke.py`` — prints one JSON line)
or via tier-1 (``tests/test_obs.py::test_obs_smoke`` imports
:func:`run_smoke`). Fast: small batches, CPU backend, ephemeral ports.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import urllib.request


def run_smoke() -> dict:
    """Drive the stack; returns summary facts (raises on any failure)."""
    from tpubloom import checkpoint as ckpt
    from tpubloom.obs.exposition import parse_families
    from tpubloom.obs.httpd import start_metrics_server
    from tpubloom.server.client import BloomClient
    from tpubloom.server.service import BloomService, build_server

    ckpt_dir = tempfile.mkdtemp(prefix="tpubloom-obs-smoke-")
    service = BloomService(sink_factory=lambda config: ckpt.FileSink(ckpt_dir))
    server, port = build_server(service, "127.0.0.1:0")
    server.start()
    metrics_server = start_metrics_server(service, port=0, host="127.0.0.1")
    try:
        client = BloomClient(f"127.0.0.1:{port}")
        client.wait_ready()
        client.create_filter(
            "smoke", capacity=50_000, error_rate=0.01, checkpoint_every=1000
        )
        keys = [b"smoke-key-%06d" % i for i in range(2048)]
        assert client.insert_batch("smoke", keys) == len(keys)
        insert_rid = client.last_rid
        assert client.include_batch("smoke", keys[:256]).all()
        client.checkpoint("smoke", wait=True)

        with urllib.request.urlopen(
            f"http://127.0.0.1:{metrics_server.port}/metrics", timeout=10
        ) as resp:
            assert resp.status == 200
            text = resp.read().decode()
        families = parse_families(text)

        required = [
            "tpubloom_keys_inserted_total",
            "tpubloom_rpc_duration_seconds_bucket",
            "tpubloom_rpc_phase_seconds_bucket",
            "tpubloom_filter_fill_ratio",
            "tpubloom_filter_fpr_drift",
            "tpubloom_checkpoint_lag_inserts",
            "tpubloom_checkpoint_age_seconds",
            "tpubloom_slowlog_entries",
        ]
        missing = [name for name in required if name not in families]
        assert not missing, f"/metrics scrape is missing {missing}"
        assert families["tpubloom_keys_inserted_total"][()] == len(keys)

        entries = client.slowlog_get()
        assert entries, "slowlog must be non-empty after traffic"
        rids = {e["rid"] for e in entries}
        assert insert_rid in rids, "client rid must appear in the slowlog"
        phased = [e for e in entries if e["method"] == "InsertBatch"]
        assert phased and {"decode", "host_prep", "kernel"} <= set(
            phased[0]["phases"]
        )

        # -- tracing phase (ISSUE 15) ---------------------------------
        from tpubloom.obs import trace as trace_mod

        def measure(cl, tag):
            # equal-length tags keep every run on ONE padded key shape —
            # the warm-up batch eats the jit compile so neither side's
            # window measures compilation (the re-learned PR-10 lesson)
            batch = [b"trace-%s-%%06d" % tag % i for i in range(256)]
            cl.insert_batch("smoke", batch)
            t0 = time.perf_counter()
            for _ in range(20):
                cl.insert_batch("smoke", batch)
            return 20 / (time.perf_counter() - t0)

        # sampling OFF (this server booted without a trace knob): the
        # client must stamp NO wire field and the ring must stay off
        assert not trace_mod.enabled()
        seen_reqs = []
        orig_call = client._call_once

        def spy(method, req, *a, **kw):
            seen_reqs.append(dict(req))
            return orig_call(method, req, *a, **kw)

        client._call_once = spy
        off_rate = measure(client, b"of0")
        client._call_once = orig_call
        assert seen_reqs and all("trace" not in r for r in seen_reqs), (
            "the sampling-off path must add no wire fields"
        )
        off_rid = client.last_rid
        assert client._rpc("TraceGet", {"trace_rid": off_rid}) == {
            "ok": True, "rid": off_rid, "enabled": False, "spans": [],
        }

        # sampling ON at 1.0: spans land; overhead stays bounded.
        # Generous bound + re-measure-once — this is an anti-regression
        # gate on a noisy shared runner, not a microbenchmark.
        trace_mod.configure(sample=1.0)
        traced_client = BloomClient(f"127.0.0.1:{port}", trace_sample=1.0)
        try:
            on_rate = measure(traced_client, b"on0")
            if on_rate < 0.5 * off_rate:
                # re-measure BOTH sides honestly: the off baseline must
                # run with the ring disarmed again — at sample 1.0 the
                # server captures the untraced client's requests too,
                # and a traced-vs-traced comparison would pass exactly
                # when a real regression triggered this branch
                trace_mod.configure(None)
                off_rate = measure(client, b"of1")
                trace_mod.configure(sample=1.0)
                on_rate = measure(traced_client, b"on1")
            assert on_rate >= 0.4 * off_rate, (
                f"tracing overhead out of bounds: on={on_rate:.1f}/s "
                f"vs off={off_rate:.1f}/s"
            )
            spans = traced_client.trace_get(traced_client.last_rid)
            span_names = {s["name"] for s in spans}
            assert {"rpc.InsertBatch", "client.hop",
                    "phase.kernel"} <= span_names, span_names
        finally:
            trace_mod.reset_for_tests()

        # -- crash-forensics black box (ISSUE 16) ---------------------
        # disabled is the default and the disabled path is the same
        # one-truthy-check-per-note path the earlier phases already
        # measured; the gate here bounds the ENABLED cost: mmap'd
        # write-through flight notes plus forced/slow span spills.
        from tpubloom.obs import blackbox as bb_mod

        assert not bb_mod.enabled(), "black box must be off by default"
        bb_dir = tempfile.mkdtemp(prefix="tpubloom-obs-smoke-bb-")
        try:
            bb_off_rate = measure(client, b"bf0")
            assert bb_mod.configure(bb_dir, node={"addr": "smoke"})
            # sample 0.0 arms the ring without sampling anything: only
            # the slow-probe path captures — and a freshly reset slowlog
            # makes the first timed batches all slowlog-worthy, so the
            # window measures real spills, not an idle ring
            trace_mod.configure(sample=0.0)
            service.slowlog.reset()
            bb_on_rate = measure(client, b"bn0")
            if bb_on_rate < 0.5 * bb_off_rate:
                trace_mod.reset_for_tests()
                bb_off_rate = measure(client, b"bf1")
                trace_mod.configure(sample=0.0)
                service.slowlog.reset()
                bb_on_rate = measure(client, b"bn1")
            assert bb_on_rate >= 0.4 * bb_off_rate, (
                f"black-box overhead out of bounds: on={bb_on_rate:.1f}/s "
                f"vs off={bb_off_rate:.1f}/s"
            )
            bb_mod.sync()
            node = bb_mod.read_node(bb_dir)
            assert node["spans"], "slowlog-worthy spans must have spilled"
            assert node["meta"].get("pid") == os.getpid()
            assert not node["skipped"], "a live ring must decode cleanly"
        finally:
            trace_mod.reset_for_tests()
            bb_mod.reset_for_tests()

        return {
            "ok": True,
            "metrics_families": len(families),
            "scrape_bytes": len(text),
            "slowlog_entries": len(entries),
            "insert_rid_correlated": True,
            "keys_inserted_total": int(
                families["tpubloom_keys_inserted_total"][()]
            ),
            "trace_off_wire_clean": True,
            "trace_off_rate_per_s": round(off_rate, 1),
            "trace_on_rate_per_s": round(on_rate, 1),
            "trace_overhead_ratio": round(on_rate / off_rate, 3),
            "trace_spans_sampled": len(spans),
            "blackbox_off_rate_per_s": round(bb_off_rate, 1),
            "blackbox_on_rate_per_s": round(bb_on_rate, 1),
            "blackbox_overhead_ratio": round(bb_on_rate / bb_off_rate, 3),
            "blackbox_spans_spilled": len(node["spans"]),
        }
    finally:
        metrics_server.close()
        server.stop(grace=None)


def main() -> None:
    print(json.dumps(run_smoke()))


if __name__ == "__main__":
    # a CPU smoke: standalone runs stay off the chip (set before jax
    # initializes a backend)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
