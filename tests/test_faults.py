"""Chaos suite (ISSUE 2): drive the stack through injected faults and
pin the hardening they exposed.

Layers covered:

* the fault framework itself — deterministic policies, env arming,
  injection counters;
* CRC32C — published test vectors (the portable NumPy slicing-by-8 path
  must equal any C accelerator bit-for-bit);
* checkpoint v2 — corrupt/torn/truncated newest generation falls back to
  the previous one, quarantines the corpse, never leaves partial files
  (tmp+rename invariant under injected fsync faults), retention GC;
* server — restore-past-corruption keeps serving and walks
  DEGRADED -> SERVING; overload shedding with ``retry_after_ms``;
  graceful-drain admission (DRAINING sheds);
* client — shed-aware retries complete every call, DeleteBatch replays
  dedup instead of double-decrementing, the circuit breaker opens after
  consecutive transport failures and closes through a half-open probe.
"""

import os
import socket
import threading
import time

import grpc
import numpy as np
import pytest

from tpubloom import checkpoint as ckpt
from tpubloom import faults
from tpubloom.config import FilterConfig
from tpubloom.filter import BloomFilter
from tpubloom.obs import counters as obs_counters
from tpubloom.server.client import BloomClient, CircuitOpenError
from tpubloom.server.protocol import BloomServiceError
from tpubloom.server.service import BloomService, build_server
from tpubloom.utils.crc32c import crc32c

# ISSUE 6: the whole chaos module runs with the runtime lock-order /
# held-while-blocking tracker armed (in-process AND subprocess servers);
# teardown asserts zero violations — see tests/conftest.py. ISSUE 13:
# additionally gated on the declared lock-ORDER manifest — an
# undeclared acquisition edge anywhere in the armed run fails the
# module too.
pytestmark = pytest.mark.usefixtures("lock_check_armed", "lock_order_manifest")


@pytest.fixture(autouse=True)
def _disarm_all():
    faults.reset()
    yield
    faults.reset()


def _rand_keys(n, rng):
    return [rng.bytes(16) for _ in range(n)]


def _filter_with_keys(cfg, n=500, seed=0):
    f = BloomFilter(cfg)
    keys = _rand_keys(n, np.random.default_rng(seed))
    f.insert_batch(keys)
    return f, keys


# -- fault framework ---------------------------------------------------------


def test_fire_is_noop_when_disarmed():
    assert faults.fire("ckpt.write") is None


def test_unknown_point_and_bad_policy_rejected():
    with pytest.raises(ValueError, match="unknown fault point"):
        faults.arm("ckpt.wirte")  # typo must fail loudly
    with pytest.raises(ValueError, match="unknown fault policy"):
        faults.arm("ckpt.write", "sometimes")
    with pytest.raises(ValueError, match="unknown fault mode"):
        faults.arm("ckpt.write", mode="explode")


def test_once_policy_fires_exactly_once():
    faults.arm("rpc.pre_handle", "once")
    with pytest.raises(faults.InjectedFault):
        faults.fire("rpc.pre_handle")
    for _ in range(5):
        assert faults.fire("rpc.pre_handle") is None
    (desc,) = faults.active()
    assert desc["fired"] == 1


def test_nth_policy_period():
    faults.arm("rpc.pre_handle", "nth:3")
    hits = []
    for i in range(1, 10):
        try:
            faults.fire("rpc.pre_handle")
        except faults.InjectedFault:
            hits.append(i)
    assert hits == [3, 6, 9]


def test_probability_policy_is_seed_deterministic():
    def run():
        faults.arm("rpc.pre_handle", "prob:0.5:seed=42")
        pattern = []
        for _ in range(64):
            try:
                faults.fire("rpc.pre_handle")
                pattern.append(0)
            except faults.InjectedFault:
                pattern.append(1)
        return pattern

    a, b = run(), run()
    assert a == b, "seeded chaos must replay byte-identically"
    assert 10 < sum(a) < 54  # and actually mix faults with passes


def test_times_cap_bounds_any_policy():
    faults.arm("rpc.pre_handle", "always", times=2)
    fired = 0
    for _ in range(10):
        try:
            faults.fire("rpc.pre_handle")
        except faults.InjectedFault:
            fired += 1
    assert fired == 2


def test_env_var_arming(monkeypatch):
    monkeypatch.setenv(
        faults.ENV_VAR, "ckpt.fsync=once, rpc.pre_handle=nth:2:times=1"
    )
    faults.load_env(force=True)
    armed = {d["point"]: d for d in faults.active()}
    assert armed["ckpt.fsync"]["times"] == 1
    assert armed["rpc.pre_handle"]["policy"] == "nth:2"


def test_injection_counters():
    before = obs_counters.get("faults_injected")
    faults.arm("ckpt.restore_read", "once")
    with pytest.raises(faults.InjectedFault):
        faults.fire("ckpt.restore_read")
    assert obs_counters.get("faults_injected") == before + 1
    assert obs_counters.get("fault_ckpt_restore_read") >= 1


# -- CRC32C ------------------------------------------------------------------


def test_crc32c_published_vectors():
    # RFC 3720 / kernel crypto test vectors
    assert crc32c(b"") == 0x00000000
    assert crc32c(b"a") == 0xC1D04330
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(bytes(32)) == 0x8A9136AA
    assert crc32c(bytes(range(32))) == 0x46DD794E
    assert (
        crc32c(b"The quick brown fox jumps over the lazy dog") == 0x22620404
    )


def test_crc32c_streaming_continuation():
    rng = np.random.default_rng(3)
    blob = rng.bytes(100_003)  # odd length: exercises the tail loop
    whole = crc32c(blob)
    assert whole == crc32c(blob[40_000:], crc32c(blob[:40_000]))
    assert whole != crc32c(blob[:-1])


# -- checkpoint v2: corruption tolerance -------------------------------------


@pytest.fixture()
def cfg():
    return FilterConfig(m=1 << 14, k=5, key_len=16, key_name="chaos")


def _flip_byte(path: str, offset: int = -3):
    blob = bytearray(open(path, "rb").read())
    blob[offset] ^= 0xFF
    open(path, "wb").write(bytes(blob))


def test_corrupt_newest_falls_back_a_generation(cfg, tmp_path):
    sink = ckpt.FileSink(str(tmp_path))
    f, keys = _filter_with_keys(cfg)
    ckpt.save(f, sink, seq=1)
    f.insert_batch([b"tail-key-0000000"])
    ckpt.save(f, sink, seq=2)

    _flip_byte(sink._path("chaos", 2))  # payload bit rot
    before = obs_counters.get("ckpt_corrupt_detected")
    g = ckpt.restore(cfg, sink)
    assert g is not None and g._restored_seq == 1
    assert g.include_batch(keys).all()
    assert obs_counters.get("ckpt_corrupt_detected") == before + 1
    # the corpse is quarantined, not deleted (post-mortem material) and a
    # re-walk goes straight to the good generation
    qfile = tmp_path / "corrupt" / "chaos.000000000002.ckpt"
    assert qfile.exists()
    assert ckpt.restore(cfg, sink)._restored_seq == 1
    assert obs_counters.get("ckpt_corrupt_detected") == before + 1


def test_header_corruption_detected(cfg, tmp_path):
    sink = ckpt.FileSink(str(tmp_path))
    f, _ = _filter_with_keys(cfg)
    ckpt.save(f, sink, seq=1)
    path = sink._path("chaos", 1)
    _flip_byte(path, offset=len(ckpt.MAGIC_V2) + 12 + 4)  # inside header
    with pytest.raises(ckpt.CheckpointCorruptError, match="header"):
        ckpt._deserialize(open(path, "rb").read())
    assert ckpt.restore(cfg, sink) is None  # only generation is corrupt


def test_truncated_blob_detected(cfg, tmp_path):
    sink = ckpt.FileSink(str(tmp_path))
    f, keys = _filter_with_keys(cfg)
    ckpt.save(f, sink, seq=1)
    ckpt.save(f, sink, seq=2)
    path = sink._path("chaos", 2)
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[: len(blob) // 2])
    g = ckpt.restore(cfg, sink)
    assert g._restored_seq == 1 and g.include_batch(keys).all()


def test_torn_write_fault_caught_on_restore(cfg, tmp_path):
    """mode=torn: the write 'succeeds' but half the blob is gone — only
    the CRC walk can notice. The previous generation must win."""
    sink = ckpt.FileSink(str(tmp_path))
    f, keys = _filter_with_keys(cfg)
    ckpt.save(f, sink, seq=1)
    faults.arm("ckpt.write", "once", mode="torn")
    ckpt.save(f, sink, seq=2)  # no exception: silent corruption
    g = ckpt.restore(cfg, sink)
    assert g._restored_seq == 1 and g.include_batch(keys).all()


def test_fsync_fault_leaves_no_partial_ckpt(cfg, tmp_path):
    """Kill-mid-checkpoint invariant: a failure before fsync+rename must
    leave neither a final .ckpt nor a stale .tmp behind."""
    sink = ckpt.FileSink(str(tmp_path))
    f, keys = _filter_with_keys(cfg)
    ckpt.save(f, sink, seq=1)
    faults.arm("ckpt.fsync", "always")
    with pytest.raises(faults.InjectedFault):
        ckpt.save(f, sink, seq=2)
    faults.reset()
    names = set(os.listdir(tmp_path))
    assert names == {"chaos.000000000001.ckpt"}, names
    assert ckpt.restore(cfg, sink)._restored_seq == 1


def test_restore_read_fault_skips_generation(cfg, tmp_path):
    sink = ckpt.FileSink(str(tmp_path))
    f, keys = _filter_with_keys(cfg)
    ckpt.save(f, sink, seq=1)
    ckpt.save(f, sink, seq=2)
    before = obs_counters.get("ckpt_restore_read_errors")
    faults.arm("ckpt.restore_read", "once")
    g = ckpt.restore(cfg, sink)
    assert g._restored_seq == 1
    assert obs_counters.get("ckpt_restore_read_errors") == before + 1
    # NOT quarantined — the bytes may be fine, only the read failed
    assert not (tmp_path / "corrupt").exists()
    assert ckpt.restore(cfg, sink)._restored_seq == 2


def test_config_mismatch_is_not_skippable(cfg, tmp_path):
    """The walk must NOT paper over an operator error: a config identity
    mismatch raises even though an older (also mismatched) blob exists."""
    sink = ckpt.FileSink(str(tmp_path))
    f, _ = _filter_with_keys(cfg)
    ckpt.save(f, sink, seq=1)
    ckpt.save(f, sink, seq=2)
    with pytest.raises(ValueError, match="mismatch on k"):
        ckpt.restore(cfg.replace(k=cfg.k + 1), sink)


def test_async_checkpointer_retention_gc(cfg, tmp_path):
    sink = ckpt.FileSink(str(tmp_path))
    f, keys = _filter_with_keys(cfg)
    cp = ckpt.AsyncCheckpointer(f, sink, retain=2)
    for _ in range(5):
        assert cp.trigger()
        assert cp.flush()
    cp.close(final_checkpoint=False)
    assert len(sink.list_seqs("chaos")) == 2
    assert ckpt.restore(cfg, sink).include_batch(keys).all()


def test_v1_blob_still_restores(cfg, tmp_path):
    """Read-compat: a pre-ISSUE-2 writer's blob (TPUBLOOM1, no CRC) must
    keep restoring."""
    import json

    sink = ckpt.FileSink(str(tmp_path))
    f, keys = _filter_with_keys(cfg)
    ckpt.save(f, sink, seq=1)
    path = sink._path("chaos", 1)
    header, payload = ckpt._deserialize(open(path, "rb").read())
    header.pop("payload_len"), header.pop("payload_crc32c")
    hdr = json.dumps(header).encode()
    open(path, "wb").write(
        ckpt.MAGIC + len(hdr).to_bytes(8, "little") + hdr + payload
    )
    g = ckpt.restore(cfg, sink)
    assert g._restored_seq == 1 and g.include_batch(keys).all()


# -- server: restore-past-corruption + health walk ---------------------------


def _start(tmp_path, port=0, **service_kw):
    service = BloomService(
        sink_factory=lambda config: ckpt.FileSink(str(tmp_path)), **service_kw
    )
    srv, bound = build_server(service, f"127.0.0.1:{port}")
    srv.start()
    return srv, service, bound


def test_server_restores_past_corrupt_newest_and_recovers_health(tmp_path):
    srv, service, port = _start(tmp_path)
    client = BloomClient(f"127.0.0.1:{port}")
    client.wait_ready()
    try:
        client.create_filter("c1", capacity=50_000, error_rate=0.01)
        rng = np.random.default_rng(5)
        durable = _rand_keys(1500, rng)
        client.insert_batch("c1", durable)
        client.checkpoint("c1", wait=True)  # generation A (good)
        tail = _rand_keys(500, rng)
        client.insert_batch("c1", tail)
        client.checkpoint("c1", wait=True)  # generation B (will corrupt)
    finally:
        client.close()
        srv.stop(grace=None)
    del service

    sink = ckpt.FileSink(str(tmp_path))
    seqs = sink.list_seqs("c1")
    assert len(seqs) >= 2
    _flip_byte(sink._path("c1", seqs[0]))

    srv2, service2, port2 = _start(tmp_path)
    client = BloomClient(f"127.0.0.1:{port2}")
    client.wait_ready()
    try:
        r = client.create_filter(
            "c1", capacity=50_000, error_rate=0.01, exist_ok=True
        )
        # fell back to generation A: checkpointed keys are there, the
        # server keeps serving
        assert client.include_batch("c1", durable).all()
        h = client.health()
        assert h["status"] == "DEGRADED"
        assert "checkpoint_corrupt:c1" in h["reasons"]
        assert (tmp_path / "corrupt").exists()
        # a DEGRADED server IS serving: readiness must not hang on it
        # (only accept_degraded=False insists on fully healthy)
        assert client.wait_ready(timeout=5)["status"] == "DEGRADED"
        with pytest.raises(TimeoutError):
            client.wait_ready(timeout=0.4, poll=0.05, accept_degraded=False)
        # writes still work while degraded...
        client.insert_batch("c1", [b"while-degraded00"])
        assert client.include("c1", b"while-degraded00")
        # ...and a fresh good checkpoint clears the degradation
        client.checkpoint("c1", wait=True)
        assert client.health()["status"] == "SERVING"
    finally:
        client.close()
        srv2.stop(grace=None)


# -- server: overload shedding + drain ---------------------------------------


def _slow_wrap(service, method, delay):
    orig = getattr(service, method)

    def slow(req):
        time.sleep(delay)
        return orig(req)

    setattr(service, method, slow)


def test_shed_surfaces_retry_after_ms(tmp_path):
    srv, service, port = _start(
        tmp_path, max_in_flight=2, retry_after_ms=37
    )
    _slow_wrap(service, "QueryBatch", 0.4)
    client = BloomClient(f"127.0.0.1:{port}")
    client.wait_ready()
    raw = BloomClient(f"127.0.0.1:{port}", max_retries=0)
    try:
        client.create_filter("shed", capacity=10_000, error_rate=0.01)
        keys = [b"k%015d" % i for i in range(64)]
        client.insert_batch("shed", keys)

        sheds, oks, errs = [], [], []

        def probe():
            try:
                oks.append(raw.include_batch("shed", keys))
            except BloomServiceError as e:
                (sheds if e.code == "RESOURCE_EXHAUSTED" else errs).append(e)

        threads = [threading.Thread(target=probe) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert sheds, "cap 2 with 6 concurrent slow queries must shed"
        # adaptive since ISSUE 3: the hint starts at the configured base
        # and grows with the shed rate — never below the base
        assert all(
            e.details.get("retry_after_ms", 0) >= 37 for e in sheds
        )
        assert len(oks) + len(sheds) == 6
        assert service.metrics.snapshot()["counters"]["requests_shed"] >= len(
            sheds
        )
        # Health answers DURING overload (unsheddable) and reports it
        h = client.health()
        assert h["max_in_flight"] == 2
        assert "shedding" in h["reasons"] and h["status"] == "DEGRADED"
    finally:
        raw.close()
        client.close()
        srv.stop(grace=None)


def test_retrying_clients_complete_under_shed_with_no_double_deletes(tmp_path):
    """The ISSUE-2 acceptance scenario: cap 2, slow handlers, every call
    completes via shed-aware retries, and deletes apply exactly once."""
    srv, service, port = _start(
        tmp_path, max_in_flight=2, retry_after_ms=20
    )
    _slow_wrap(service, "DeleteBatch", 0.15)
    client = BloomClient(f"127.0.0.1:{port}")
    client.wait_ready()
    try:
        client.create_filter(
            "cnt", capacity=20_000, error_rate=0.01, counting=True
        )
        keys = [b"dup%013d" % i for i in range(40)]
        client.insert_batch("cnt", keys)
        client.insert_batch("cnt", keys)  # every key at count 2

        workers = []
        failures = []
        chunks = [keys[i::8] for i in range(8)]

        def delete_chunk(chunk):
            try:
                c = BloomClient(
                    f"127.0.0.1:{port}", max_retries=10, backoff_base=0.02
                )
                try:
                    c.delete_batch("cnt", chunk)  # one delete per key
                finally:
                    c.close()
            except Exception as e:  # noqa: BLE001 — collected for assert
                failures.append(e)

        for chunk in chunks:
            t = threading.Thread(target=delete_chunk, args=(chunk,))
            workers.append(t)
            t.start()
        for t in workers:
            t.join()
        assert not failures, failures
        assert service.metrics.snapshot()["counters"]["requests_shed"] > 0
        # count 2 - exactly 1 delete = 1 -> every key still present; a
        # double-applied delete would read absent here
        assert client.include_batch("cnt", keys).all()
        # and one more delete round empties them (proves the first round
        # really applied once, not zero times)
        client.delete_batch("cnt", keys)
        assert not client.include_batch("cnt", keys).any()
    finally:
        client.close()
        srv.stop(grace=None)


def test_draining_sheds_and_health_reports(tmp_path):
    srv, service, port = _start(tmp_path)
    client = BloomClient(f"127.0.0.1:{port}", max_retries=1, backoff_base=0.01)
    client.wait_ready()
    try:
        client.create_filter("d", capacity=1000, error_rate=0.01)
        service.begin_drain()
        assert client.health()["status"] == "DRAINING"
        with pytest.raises(BloomServiceError, match="DRAINING"):
            client.insert_batch("d", [b"late"])
    finally:
        client.close()
        srv.stop(grace=None)


# -- service-level delete dedup ---------------------------------------------


def test_delete_dedup_replay_answers_from_cache(tmp_path):
    service = BloomService()
    service.CreateFilter(
        {"name": "cnt", "capacity": 10_000, "error_rate": 0.01,
         "options": {"counting": True}}
    )
    keys = [b"x%015d" % i for i in range(16)]
    service.InsertBatch({"name": "cnt", "keys": keys})
    req = {"name": "cnt", "keys": keys, "rid": "rid-logical-1"}
    r1 = service.DeleteBatch(req)
    r2 = service.DeleteBatch(req)  # replay of the same logical call
    assert r1 == r2
    # single-decrement: keys were at count 1, one delete -> absent; a
    # second APPLY would have underflowed/decremented a fresh insert
    hits = service.QueryBatch({"name": "cnt", "keys": keys})
    assert not np.unpackbits(
        np.frombuffer(hits["hits"], np.uint8), count=hits["n"]
    ).any()
    service.InsertBatch({"name": "cnt", "keys": keys})
    hits = service.QueryBatch({"name": "cnt", "keys": keys})
    assert np.unpackbits(
        np.frombuffer(hits["hits"], np.uint8), count=hits["n"]
    ).all()
    assert (
        service.metrics.snapshot()["counters"]["delete_dedup_hits"] == 1
    )


def test_client_retries_delete_after_transport_loss(tmp_path):
    """Response-lost-after-apply: the first DeleteBatch applies but the
    client sees a transport error; the auto-retry replays the rid and the
    dedup cache answers — net effect exactly one decrement."""
    srv, service, port = _start(tmp_path)
    client = BloomClient(f"127.0.0.1:{port}", backoff_base=0.01)
    client.wait_ready()

    class LostResponse(grpc.RpcError):
        def code(self):
            return grpc.StatusCode.UNAVAILABLE

    real_call = client._call_once
    dropped = []

    def flaky(method, req, timeout=None):
        resp = real_call(method, req, timeout=timeout)
        if method == "DeleteBatch" and not dropped:
            dropped.append(req["rid"])
            raise LostResponse()  # the apply landed; the answer did not
        return resp

    client._call_once = flaky
    try:
        client.create_filter(
            "cnt2", capacity=10_000, error_rate=0.01, counting=True
        )
        keys = [b"y%015d" % i for i in range(16)]
        client.insert_batch("cnt2", keys)
        client.insert_batch("cnt2", keys)  # count 2
        client.delete_batch("cnt2", keys)  # applied once + replayed once
        assert dropped, "the chaos shim must have dropped one response"
        assert client.include_batch("cnt2", keys).all(), (
            "double-applied delete: replay was re-executed, not deduped"
        )
        counters = service.metrics.snapshot()["counters"]
        assert counters["delete_dedup_hits"] == 1
    finally:
        client.close()
        srv.stop(grace=None)


# -- client circuit breaker --------------------------------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_breaker_opens_after_consecutive_failures_then_recovers(tmp_path):
    port = _free_port()
    client = BloomClient(
        f"127.0.0.1:{port}",
        max_retries=0,
        timeout=2,
        breaker_threshold=2,
        breaker_cooldown=0.4,
    )
    try:
        for _ in range(2):
            with pytest.raises(grpc.RpcError):
                client.health()
        assert client.breaker.state == "open"
        # fail-fast: no network, no backoff, no timeout wait
        t0 = time.monotonic()
        with pytest.raises(CircuitOpenError):
            client.health()
        assert time.monotonic() - t0 < 0.05
        assert obs_counters.get_gauge("client_breaker_state") == 2

        # server appears on the port; once the cooldown elapses AND the
        # gRPC channel's own reconnect backoff lets a probe through, the
        # half-open probe closes the circuit (a failed probe re-opens and
        # the next cooldown retries — hence the poll loop)
        srv, service, _ = _start(tmp_path, port=port)
        try:
            deadline = time.monotonic() + 15
            h = None
            while time.monotonic() < deadline:
                try:
                    h = client.health()
                    break
                except (grpc.RpcError, CircuitOpenError):
                    time.sleep(0.2)
            assert h is not None and h["status"] == "SERVING"
            assert client.breaker.state == "closed"
            assert obs_counters.get_gauge("client_breaker_state") == 0
        finally:
            srv.stop(grace=None)
    finally:
        client.close()


def test_breaker_halfopen_failure_reopens():
    port = _free_port()
    client = BloomClient(
        f"127.0.0.1:{port}",
        max_retries=0,
        timeout=2,
        breaker_threshold=1,
        breaker_cooldown=0.2,
    )
    try:
        with pytest.raises(grpc.RpcError):
            client.health()
        assert client.breaker.state == "open"
        time.sleep(0.25)
        with pytest.raises(grpc.RpcError):  # half-open probe fails too
            client.health()
        assert client.breaker.state == "open"
    finally:
        client.close()


def test_breaker_disabled_with_zero_threshold():
    client = BloomClient(
        "127.0.0.1:1", max_retries=0, timeout=1, breaker_threshold=0
    )
    try:
        for _ in range(3):
            with pytest.raises(grpc.RpcError):
                client.health()
        assert client.breaker.state == "closed"
    finally:
        client.close()


# -- wait_ready polls Health -------------------------------------------------


def test_wait_ready_blocks_until_serving(tmp_path):
    srv, service, port = _start(tmp_path)
    client = BloomClient(f"127.0.0.1:{port}")
    try:
        h = client.wait_ready()
        assert h["status"] == "SERVING"
        service.begin_drain()  # DRAINING is never ready -> times out
        with pytest.raises(TimeoutError, match="not ready"):
            client.wait_ready(timeout=0.5, poll=0.05)
    finally:
        client.close()
        srv.stop(grace=None)


# -- SIGTERM graceful drain (real process, real signal) ----------------------

_SERVER_CHILD = """\
import sys
import jax
jax.config.update("jax_platforms", "cpu")
from tpubloom.server.service import main
main(sys.argv[1:])
"""


def test_sigterm_drain_checkpoints_acked_state(tmp_path):
    """Kill -TERM a real server that has acked inserts but never
    checkpointed: the drain must write a final checkpoint (acked state
    survives) and exit 0."""
    import signal
    import subprocess
    import sys as _sys

    port = _free_port()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    ckpt_dir = tmp_path / "ck"
    ckpt_dir.mkdir()
    script = tmp_path / "server_child.py"
    script.write_text(_SERVER_CHILD)
    proc = subprocess.Popen(
        [_sys.executable, str(script), str(port), str(ckpt_dir)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
    )
    client = BloomClient(f"127.0.0.1:{port}")
    try:
        client.wait_ready(timeout=90)
        client.create_filter("drain", capacity=20_000, error_rate=0.01)
        keys = _rand_keys(800, np.random.default_rng(17))
        client.insert_batch("drain", keys)  # acked, NOT checkpointed

        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
        assert proc.returncode == 0, f"drain exited {proc.returncode}:\n{out[-3000:]}"

        sink = ckpt.FileSink(str(ckpt_dir))
        cfg = FilterConfig.from_capacity(20_000, 0.01, key_name="drain")
        g = ckpt.restore(cfg, sink)
        assert g is not None, "drain wrote no final checkpoint"
        assert g.include_batch(keys).all(), (
            "acked-but-unflushed inserts lost across graceful drain"
        )
    finally:
        client.close()
        if proc.poll() is None:
            proc.kill()


# -- chaos smoke (tier-1 wrapper around benchmarks/faults_smoke.py) ----------


def test_faults_smoke():
    """The benchmarks/faults_smoke.py end-to-end chaos check runs in
    tier-1 so the fault hooks cannot silently rot."""
    import importlib
    import sys

    bench_dir = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, os.path.abspath(bench_dir))
    try:
        faults_smoke = importlib.import_module("faults_smoke")
        result = faults_smoke.run_smoke()
    finally:
        sys.path.pop(0)
    assert result["restored_past_corruption"]
    assert result["sheds"] > 0
    assert result["deletes_double_applied"] == 0


# -- per-shard fault points (ISSUE 4 satellite) ------------------------------


def _routes_of(cfg, keys):
    """Host-side shard routing of each key (mirrors the device hash)."""
    import jax.numpy as jnp

    from tpubloom.ops import hashing
    from tpubloom.utils.packing import pack_keys

    keys_u8, lengths = pack_keys(keys, cfg.key_len)
    return np.asarray(
        hashing.route_shards(
            jnp.asarray(keys_u8),
            jnp.asarray(np.maximum(lengths, 0)),
            n_shards=cfg.shards,
            seed=cfg.seed,
        )
    )


def test_shard_fault_point_predicate_partial_failure():
    """The ISSUE-4 chaos contract: ``shard.insert`` with a ``shard=N``
    predicate fails ONLY batches that route a key to shard N — every
    other shard keeps serving (partial failure, not an outage)."""
    from tpubloom.parallel.sharded import ShardedBloomFilter

    cfg = FilterConfig(m=1 << 20, k=4, key_len=16, shards=8)
    f = ShardedBloomFilter(cfg)
    rng = np.random.default_rng(11)
    keys = _rand_keys(256, rng)
    routes = _routes_of(cfg, keys)
    target = int(routes[0])
    hit = [k for k, r in zip(keys, routes) if r == target]
    miss = [k for k, r in zip(keys, routes) if r != target][:32]
    assert hit and miss, "batch did not spread over shards"

    faults.arm("shard.insert", "always", pred={"shard": target})
    # a batch touching the target shard dies...
    with pytest.raises(faults.InjectedFault):
        f.insert_batch(hit[:4])
    # ...but batches routed AROUND it land fine (partial failure)
    f.insert_batch(miss)
    assert np.asarray(f.include_batch(miss)).all()
    faults.disarm("shard.insert")

    # the query path has its own point; `once` disarms after one firing
    faults.arm("shard.query", "once", pred={"shard": target})
    assert np.asarray(f.include_batch(miss)).all()  # doesn't touch target
    with pytest.raises(faults.InjectedFault):
        f.include_batch(hit[:2])
    f.include_batch(hit[:2])  # budget spent: the shard serves again


def test_shard_fault_partial_failure_chaos_sharded_server(tmp_path):
    """Partial-failure chaos end to end: a sharded filter behind the
    server keeps answering for healthy shards while one shard's insert
    path is poisoned; the client sees a structured INTERNAL error for
    poisoned batches, not a dead server — and the shard heals when the
    fault disarms."""
    service = BloomService()
    srv, port = build_server(service, "127.0.0.1:0")
    srv.start()
    client = BloomClient(f"127.0.0.1:{port}", max_retries=0)
    cfg = FilterConfig(m=1 << 20, k=4, key_len=16, shards=8)
    try:
        client.wait_ready()
        client.create_filter(
            "sh", config={"m": 1 << 20, "k": 4, "key_len": 16, "shards": 8}
        )
        rng = np.random.default_rng(12)
        keys = _rand_keys(256, rng)
        routes = _routes_of(cfg, keys)
        target = int(routes[0])
        poisoned = [k for k, r in zip(keys, routes) if r == target][:8]
        healthy = [k for k, r in zip(keys, routes) if r != target][:64]

        faults.arm("shard.insert", "always", pred={"shard": target})
        with pytest.raises(BloomServiceError, match="INTERNAL"):
            client.insert_batch("sh", poisoned)
        client.insert_batch("sh", healthy)  # unaffected shards serve
        assert client.include_batch("sh", healthy).all()
        assert not client.include_batch("sh", poisoned).any()
        assert obs_counters.get("fault_shard_insert") >= 1

        faults.disarm("shard.insert")  # the shard heals
        client.insert_batch("sh", poisoned)
        assert client.include_batch("sh", poisoned).all()
    finally:
        client.close()
        srv.stop(grace=None)


def test_shard_fault_env_predicate_syntax(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "shard.insert=once:shard=3")
    faults.load_env(force=True)
    (desc,) = faults.active()
    assert desc["point"] == "shard.insert"
    assert desc["pred"] == {"shard": "3"}
    # non-matching context passes through WITHOUT consuming the budget
    assert faults.fire("shard.insert", shard=1) is None
    assert faults.fire("shard.insert") is None
    with pytest.raises(faults.InjectedFault):
        faults.fire("shard.insert", shard=3)
    assert faults.fire("shard.insert", shard=3) is None  # once spent


def test_dist_initialize_fault_point():
    from tpubloom.parallel.distributed import initialize_multihost

    faults.arm("dist.initialize", "once")
    with pytest.raises(faults.InjectedFault):
        initialize_multihost()
    topo = initialize_multihost()  # disarmed: single-host no-op
    assert topo["process_count"] >= 1


# -- ISSUE 13 (chaos-coverage closure): the response-loss + per-shard
# delete points get their own armed drives ----------------------------------


def test_rpc_post_handle_response_loss_absorbed_by_dedup():
    """``rpc.post_handle`` fires AFTER the handler applied (and the
    barrier/forward ran) but before the response encodes — the "ack lost
    in flight" case rid-dedup exists for. On a counting filter, the
    same-rid retry must answer from the cache instead of incrementing a
    second time."""
    service = BloomService()
    srv, port = build_server(service, "127.0.0.1:0")
    srv.start()
    client = BloomClient(f"127.0.0.1:{port}", max_retries=0)
    try:
        client.wait_ready()
        client.create_filter(
            "cnt", capacity=20_000, error_rate=0.01, counting=True
        )
        keys = [b"pl-%04d" % i for i in range(64)]
        req = client._encode_keys({"name": "cnt"}, keys)

        faults.arm("rpc.post_handle", "once")
        with pytest.raises(BloomServiceError, match="INTERNAL"):
            client._rpc("InsertBatch", dict(req), rid="post-handle-rid-1")
        assert obs_counters.get("fault_rpc_post_handle") >= 1
        # the apply LANDED even though the response was lost
        assert client.include_batch("cnt", keys).all()

        # same-rid retry: served from the dedup cache, no second apply
        resp = client._rpc("InsertBatch", dict(req), rid="post-handle-rid-1")
        assert resp["ok"] and resp["n"] == len(keys)
        # exactly-once proof: counts are 1, so ONE delete round empties
        client.delete_batch("cnt", keys)
        assert not client.include_batch("cnt", keys).any(), (
            "retry after rpc.post_handle double-applied the increments"
        )
    finally:
        client.close()
        srv.stop(grace=None)


def test_shard_delete_fault_point_predicate_partial_failure():
    """``shard.delete`` mirrors the insert/query chaos contract on the
    delete path: with a ``shard=N`` predicate only batches routing a key
    to shard N die, other shards keep deleting — and the poisoned
    shard's counts are untouched (no partial decrement before the
    fault: it fires host-side, before the launch)."""
    from tpubloom.parallel.sharded import ShardedBloomFilter

    cfg = FilterConfig(m=1 << 20, k=4, key_len=16, shards=8, counting=True)
    f = ShardedBloomFilter(cfg)
    rng = np.random.default_rng(13)
    keys = _rand_keys(256, rng)
    routes = _routes_of(cfg, keys)
    target = int(routes[0])
    hit = [k for k, r in zip(keys, routes) if r == target][:8]
    miss = [k for k, r in zip(keys, routes) if r != target][:32]
    assert hit and miss, "batch did not spread over shards"
    f.insert_batch(hit + miss)  # every count exactly 1

    faults.arm("shard.delete", "always", pred={"shard": target})
    # a delete touching the target shard dies WHOLE (fired pre-launch)...
    with pytest.raises(faults.InjectedFault):
        f.delete_batch(hit[:4])
    assert np.asarray(f.include_batch(hit)).all(), (
        "failed delete decremented anyway"
    )
    # ...but deletes routed around it land fine (partial failure)
    f.delete_batch(miss)
    assert not np.asarray(f.include_batch(miss)).any()
    assert obs_counters.get("fault_shard_delete") >= 1
    faults.disarm("shard.delete")

    f.delete_batch(hit)  # the shard heals: counts reach zero
    assert not np.asarray(f.include_batch(hit)).any()
