"""Streaming ingest plane suite (ISSUE 18).

Layers covered:

* **e2e round trip** — persistent ``InsertStream``/``QueryStream``
  sessions through the coalescer: per-frame acks carry the full
  unary-shaped verdicts (n / presence / hits), acks pipelined under the
  credit window, ``stream_connected_current`` back to zero on close;
* **per-frame gates** — error verdicts (NOT_FOUND, READONLY) ride the
  ack for THEIR frame and never kill the stream: the frames after a
  rejected one still apply;
* **chaos** — ``stream.recv`` (frame dropped before anything applied)
  and ``stream.ack`` (ack lost AFTER the apply): both kill the stream
  mid-flight; the client session reconnects and replays only unacked
  frames under their ORIGINAL rids, the rid→response dedup cache turns
  the already-applied replay into a cache hit, and a counting filter
  proves exactly-once (one delete fully clears every key);
* **the acceptance** — a real subprocess server SIGKILLed with a
  stream's frames in flight, restarted over the same op-log dir: the
  session replays the unacked tail, every frame acks OK, every key is
  readable EXACTLY once on a counting filter, and the killed process's
  black-box ring (PR 16) is readable post-mortem.

Armed under the lock tracker + lock-order manifest like the other
chaos modules (tests/conftest.py).
"""

import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from tpubloom import faults
from tpubloom.obs import counters as obs_counters
from tpubloom.server.client import BloomClient
from tpubloom.server.ingest import CoalesceConfig
from tpubloom.server.protocol import BloomServiceError
from tpubloom.server.service import BloomService, build_server

pytestmark = pytest.mark.usefixtures("lock_check_armed", "lock_order_manifest")


@pytest.fixture(autouse=True)
def _disarm_all():
    faults.reset()
    yield
    faults.reset()


class _Server:
    def __init__(self, service):
        self.service = service
        self.server, self.port = build_server(service, "127.0.0.1:0")
        self.server.start()
        self.addr = f"127.0.0.1:{self.port}"

    def client(self, **kw) -> BloomClient:
        return BloomClient(self.addr, **kw)

    def stop(self):
        self.service.shutdown()
        self.server.stop(grace=None)


@pytest.fixture()
def coalesced_server():
    s = _Server(BloomService(
        coalesce=CoalesceConfig(max_keys=4096, max_wait_us=2000)
    ))
    yield s
    s.stop()


def _counters(service):
    return service.metrics.snapshot()["counters"]


# -- e2e round trip ----------------------------------------------------------


def test_insert_and_query_stream_roundtrip(coalesced_server):
    svc = coalesced_server.service
    c = coalesced_server.client()
    try:
        c.wait_ready()
        c.create_filter("s", capacity=100_000, error_rate=0.01)
        frames = {
            i: [b"st-%02d-%04d" % (i, j) for j in range(32)]
            for i in range(40)
        }
        with c.insert_stream("s", return_presence=True) as ins:
            seqs = {i: ins.send(keys) for i, keys in frames.items()}
            resps = ins.drain(timeout=60)
            assert len(resps) == len(frames)
            assert obs_counters.get_gauge("stream_connected_current") >= 1
            for i, seq in seqs.items():
                r = ins.result(seq)
                assert r["ok"] and r["n"] == len(frames[i])
                # fresh keys: presence is all-absent for the frame
                bits = np.unpackbits(
                    np.frombuffer(r["presence"], dtype=np.uint8)
                )[: len(frames[i])]
                assert not bits.any()
        with c.query_stream("s") as qs:
            seq_hit = qs.send(frames[0])
            seq_miss = qs.send([b"absent-%04d" % j for j in range(32)])
            qs.drain(timeout=60)
            hits = np.unpackbits(np.frombuffer(
                qs.result(seq_hit)["hits"], dtype=np.uint8
            ))[:32]
            misses = np.unpackbits(np.frombuffer(
                qs.result(seq_miss)["hits"], dtype=np.uint8
            ))[:32]
        assert hits.all() and not misses.any()
        counters = _counters(svc)
        assert counters.get("stream_frames_total", 0) >= 42
        assert counters.get("stream_acks_total", 0) >= 42
        assert counters.get("stream_InsertStream_opened", 0) >= 1
        assert counters.get("stream_QueryStream_opened", 0) >= 1
        # both sessions closed: the gauge must come back to zero
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if obs_counters.get_gauge("stream_connected_current") == 0:
                break
            time.sleep(0.02)
        assert obs_counters.get_gauge("stream_connected_current") == 0
    finally:
        c.close()


def test_streamed_frames_ride_the_coalescer(coalesced_server):
    """Concurrent streamed frames park like unary requests and flush as
    shared device launches — the plane feeds the PR-10 coalescer, it
    does not bypass it."""
    import threading

    svc = coalesced_server.service
    c = coalesced_server.client()
    try:
        c.wait_ready()
        c.create_filter("co", capacity=100_000, error_rate=0.01)
        f0 = _counters(svc).get("ingest_flushes", 0)
        r0 = _counters(svc).get("ingest_requests_coalesced", 0)

        def pump(t):
            cc = coalesced_server.client()
            try:
                with cc.insert_stream("co") as s:
                    for i in range(24):
                        s.send([b"co-%d-%d-%04d" % (t, i, j)
                                for j in range(16)])
                    s.drain(timeout=60)
            finally:
                cc.close()

        ts = [threading.Thread(target=pump, args=(t,)) for t in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        counters = _counters(svc)
        flushes = counters.get("ingest_flushes", 0) - f0
        parked = counters.get("ingest_requests_coalesced", 0) - r0
        assert parked >= 96, "streamed frames must park in the coalescer"
        assert flushes < parked, (
            f"{flushes} flushes for {parked} parked frames — frames "
            f"must share launches"
        )
    finally:
        c.close()


# -- per-frame gates ---------------------------------------------------------


def test_error_verdicts_do_not_kill_the_stream(coalesced_server):
    c = coalesced_server.client()
    try:
        c.wait_ready()
        c.create_filter("ok", capacity=10_000, error_rate=0.01)
        with c.insert_stream("ok") as s:
            good1 = s.send([b"a", b"b"])
            # mid-stream frame against a missing filter: ITS ack is the
            # error — the session keeps flowing
            bad = s.send([b"x"], name="no-such-filter")
            good2 = s.send([b"c", b"d"])
            s.drain(timeout=60)
            assert s.result(good1)["n"] == 2
            with pytest.raises(BloomServiceError, match="NOT_FOUND"):
                s.result(bad)
            assert s.result(good2)["n"] == 2
        assert c.include("ok", b"c")
    finally:
        c.close()


def test_readonly_replica_rejects_streamed_inserts():
    srv = _Server(BloomService(read_only=True))
    c = srv.client()
    try:
        c.wait_ready()
        before = _counters(srv.service).get("readonly_rejected", 0)
        with c.insert_stream("any") as s:
            seq = s.send([b"k"])
            with pytest.raises(BloomServiceError, match="READONLY"):
                s.result(seq, timeout=30)
        assert _counters(srv.service).get("readonly_rejected", 0) > before
    finally:
        c.close()
        srv.stop()


# -- chaos: mid-stream kill, reconnect, exactly-once replay ------------------


def _exactly_once(client, name, frames):
    """Counting-filter proof: every key present, ONE delete clears it —
    a double-applied frame would leave count 2 and survive the delete."""
    for keys in frames.values():
        assert client.include_batch(name, keys).all()
        client.delete_batch(name, keys)
        assert not client.include_batch(name, keys).any(), (
            "a replayed frame applied twice (count survived one delete)"
        )


def test_stream_recv_fault_reconnect_replays_unapplied(coalesced_server):
    """``stream.recv`` kills the stream BEFORE the frame touches
    anything: the session reconnects and the replay is the first (and
    only) apply."""
    svc = coalesced_server.service
    c = coalesced_server.client()
    try:
        c.wait_ready()
        c.create_filter("cnt", capacity=50_000, error_rate=0.01,
                        counting=True)
        frames = {
            i: [b"rv-%02d-%04d" % (i, j) for j in range(16)]
            for i in range(8)
        }
        with c.insert_stream("cnt") as s:
            for i in range(4):
                s.send(frames[i])
            s.drain(timeout=60)
            faults.arm("stream.recv", "once")
            for i in range(4, 8):
                s.send(frames[i])
            resps = s.drain(timeout=120)
        assert len(resps) == 8
        assert all(r.get("ok") for r in resps)
        assert obs_counters.get("fault_stream_recv") >= 1
        _exactly_once(c, "cnt", frames)
    finally:
        c.close()


def test_stream_ack_loss_after_apply_dedups_replay(coalesced_server):
    """``stream.ack`` kills the stream AFTER the flush applied but
    before the ack reached the client — the replayed frame (same rid)
    must hit the dedup cache, not re-apply."""
    svc = coalesced_server.service
    c = coalesced_server.client()
    try:
        c.wait_ready()
        c.create_filter("cnt", capacity=50_000, error_rate=0.01,
                        counting=True)
        frames = {0: [b"ak-%04d" % j for j in range(32)]}
        faults.arm("stream.ack", "once")
        with c.insert_stream("cnt") as s:
            seq = s.send(frames[0])
            s.drain(timeout=120)
            r = s.result(seq)
            assert r["ok"] and r["n"] == 32
        assert obs_counters.get("fault_stream_ack") >= 1
        assert _counters(svc).get("stream_frame_dedup_hits", 0) >= 1, (
            "the applied-then-lost frame's replay must be a dedup hit"
        )
        _exactly_once(c, "cnt", frames)
    finally:
        c.close()


# -- the acceptance: SIGKILL mid-stream --------------------------------------

_SERVER_CHILD = """\
import sys
import jax
jax.config.update("jax_platforms", "cpu")
from tpubloom.server.service import main
main(sys.argv[1:])
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _child_env():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }


def _spawn(tmp_path, script_name, args):
    script = tmp_path / script_name
    script.write_text(_SERVER_CHILD)
    return subprocess.Popen(
        [sys.executable, str(script)] + [str(a) for a in args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_child_env(),
    )


def test_sigkill_midstream_replay_is_exactly_once(tmp_path):
    """THE ISSUE-18 acceptance: SIGKILL a real subprocess server with a
    stream's frames in flight; restart it over the same op-log dir; the
    session replays ONLY the unacked frames under their original rids;
    the restarted server's dedup cache (re-seeded from the merged log
    records' ``parts``) absorbs any frame whose first flight already
    committed — every frame acks OK and a counting filter holds every
    key EXACTLY once. The killed process's black-box ring is readable
    post-mortem."""
    plog = tmp_path / "primary-log"
    port = _free_port()
    args = [port, tmp_path / "ckpt", "--repl-log-dir", plog,
            "--coalesce-max-keys", "4096", "--coalesce-max-wait-us", "2000",
            "--trace-sample", "0.0"]
    proc = _spawn(tmp_path, "server-a.py", args)
    restarted = None
    # a server restart takes seconds (jax import): give the session a
    # reconnect budget that outlasts it
    client = BloomClient(
        f"127.0.0.1:{port}", timeout=30.0,
        max_retries=120, backoff_base=0.25, backoff_max=1.0,
    )
    frames = {
        i: [b"sk-%02d-%04d" % (i, j) for j in range(32)] for i in range(24)
    }
    try:
        client.wait_ready(timeout=120)
        client.create_filter("cnt", capacity=50_000, error_rate=0.01,
                             counting=True)
        s = client.insert_stream("cnt")
        seqs = {}
        for i in range(12):
            seqs[i] = s.send(frames[i])
        s.drain(timeout=120)  # first half fully acked by server A
        for i in range(12, 24):
            seqs[i] = s.send(frames[i])
        # kill mid-stream: the tail is in flight — parked, mid-flush,
        # or acked-but-undelivered, depending on the race we lose
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        restarted = _spawn(tmp_path, "server-b.py", args)
        resps = s.drain(timeout=300)
        assert len(resps) == 24
        for i, seq in seqs.items():
            r = s.result(seq)
            assert r.get("ok") and r.get("n") == len(frames[i]), (i, r)
        s.close()
        _exactly_once(client, "cnt", frames)
    finally:
        client.close()
        for p in (proc, restarted):
            if p is not None and p.poll() is None:
                p.send_signal(signal.SIGKILL)
        for p in (proc, restarted):
            if p is not None:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass

    # post-mortem (PR 16): the KILLED server's mmap'd ring survived and
    # identifies the process that owned the stream's first half
    import json

    cli = subprocess.run(
        [sys.executable, "-m", "tpubloom.obs.blackbox", str(plog),
         "--json"],
        capture_output=True, text=True, env=_child_env(), timeout=120,
    )
    assert cli.returncode == 0, cli.stderr
    out = json.loads(cli.stdout)
    (node,) = out["nodes"]
    assert node["meta"]["role"] == "primary"
    assert "boot" in [e["kind"] for e in node["events"]]


# -- tier-1 smoke over the streaming bench phase ------------------------------


def test_streaming_bench_smoke():
    """The ISSUE-18 acceptance gate, tier-1 sized: the bidi plane must
    move frames at least as fast as unary on the same server, with
    every counted frame actually received AND acked (anti-gaming
    asserts inside run_load)."""
    sys.path.insert(
        0,
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks"),
    )
    import ingest_load

    out = ingest_load.run_load(duration_s=1.5, quorum=False)
    assert out["streaming_vs_unary"] >= ingest_load.STREAM_GATE
    assert out["stream_frames_recv"] >= out["stream_frames_sent"]
    assert out["stream_acks_recv"] >= out["stream_frames_sent"]
