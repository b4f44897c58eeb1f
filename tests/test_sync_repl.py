"""Synchronous-replication suite (ISSUE 5).

Layers covered:

* the ack path — replicas report applied cursors on the client-streaming
  ``ReplAck`` RPC; ``ReplicaSessions`` tracks per-replica acked seqs;
* the ``Wait`` RPC — Redis ``WAIT`` parity: achieved-count answers, no
  errors on short counts, keyed to the caller's last-write ``repl_seq``;
* the commit barrier — ``min_replicas_to_write`` (server default) and
  per-request ``min_replicas``: writes block after the op-log append
  until the quorum acked, timeout → ``NOT_ENOUGH_REPLICAS`` with
  ``applied: True`` (Redis semantics — no rollback), fast-fail when
  fewer replicas are even connected, Health ``DEGRADED``;
* chaos — ack-loss (``repl.ack`` drops frames in flight; the periodic
  re-ack heals on disarm), ack-stream kill (``repl.ack_recv``; the
  replica re-opens on heartbeat), slow/dead replica (write times out,
  then succeeds once the replica catches back up), and the
  dedup-replay contract (a NOT_ENOUGH_REPLICAS retry under the same
  rid re-WAITS on the same record instead of double-applying);
* observability — ``repl_acked_seq{replica}``, ``wait_blocked_current``,
  the ``wait_barrier_seconds`` histogram;
* the acceptance chaos story — with ``min_replicas=1``, SIGKILL a real
  subprocess primary the instant a quorum-acked batch returns; after
  sentinel failover every acked element is on the new primary with the
  client's rid re-drive DISABLED (``test_quorum_acked_survives_
  sigkill_without_redrive``) — and a ``min_replicas=0`` control run
  proves the barrier is what provides the guarantee
  (``test_async_control_loses_unreplicated_write``).
"""

import os
import threading
import time

import pytest

from tpubloom import faults
from tpubloom.obs import counters as obs_counters
from tpubloom.obs.exposition import parse_families, render_service
from tpubloom.repl import OpLog, ReplicaApplier
from tpubloom.server.client import BloomClient, fetch_topology
from tpubloom.server.protocol import BloomServiceError
from tpubloom.server.service import BloomService, build_server

# ISSUE 6: armed lock-order / held-while-blocking tracking for the whole
# module (asserted violation-free at teardown — tests/conftest.py).
# ISSUE 13: plus the lock-ORDER manifest gate — every runtime
# acquisition edge this module drives must be declared.
pytestmark = pytest.mark.usefixtures("lock_check_armed", "lock_order_manifest")


@pytest.fixture(autouse=True)
def _disarm_all():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _blackbox_reset():
    """ISSUE 18 satellite: replicas arm the process-global black box
    from their state dir — unmap between tests so a ring in one test's
    tmp_path never absorbs the next test's records."""
    from tpubloom.obs import blackbox

    blackbox.reset_for_tests()
    yield
    blackbox.reset_for_tests()


def _wait(pred, timeout=30.0, poll=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    raise AssertionError(f"timed out waiting for {msg}")


def _primary(tmp_path, name="plog", **kwargs):
    oplog = OpLog(str(tmp_path / name))
    svc = BloomService(oplog=oplog, **kwargs)
    srv, port = build_server(svc, "127.0.0.1:0")
    srv.start()
    svc.listen_address = f"127.0.0.1:{port}"
    return svc, srv, port, oplog


def _replica(tmp_path, upstream_port, name=None, chained=False):
    oplog = OpLog(str(tmp_path / name)) if chained else None
    svc = BloomService(oplog=oplog, read_only=True)
    srv, port = build_server(svc, "127.0.0.1:0")
    srv.start()
    svc.listen_address = f"127.0.0.1:{port}"
    applier = ReplicaApplier(
        svc,
        f"127.0.0.1:{upstream_port}",
        reconnect_base=0.05,
        listen_address=svc.listen_address,
    ).start()
    return svc, srv, port, applier


def _warm(client, applier, oplog, name="cnt"):
    """One async write + catch-up so the replica's first-apply jit
    compile never lands inside a barrier timeout window."""
    client.insert_batch(name, [b"warmup"])
    assert applier.wait_for_seq(oplog.last_seq, 60), applier.status()


# -- Wait RPC (WAIT parity) --------------------------------------------------


def test_wait_reports_counts_never_errors(tmp_path):
    psvc, psrv, pport, poplog = _primary(tmp_path)
    c = BloomClient(f"127.0.0.1:{pport}")
    try:
        c.wait_ready()
        c.create_filter("f", capacity=1000, error_rate=0.01)
        assert c.last_write_seq == 1  # mutating responses carry repl_seq
        # no replicas: 0 achieved, immediately for numreplicas=0 ...
        assert c.wait(0) == 0
        # ... and after the timeout (not an error) for numreplicas=1
        t0 = time.monotonic()
        assert c.wait(1, timeout_ms=200) == 0
        assert 0.15 <= time.monotonic() - t0 < 5.0
    finally:
        c.close()
        psrv.stop(grace=None)
        poplog.close()


def test_wait_on_replica_unsupported(tmp_path):
    psvc, psrv, pport, poplog = _primary(tmp_path)
    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    rc = BloomClient(f"127.0.0.1:{rport}")
    try:
        with pytest.raises(BloomServiceError, match="UNSUPPORTED"):
            rc.wait(1, timeout_ms=100)
    finally:
        rc.close()
        applier.stop()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()


# -- acks + commit barrier ---------------------------------------------------


def test_quorum_write_acks_and_wait_counts(tmp_path):
    psvc, psrv, pport, poplog = _primary(tmp_path)
    c = BloomClient(f"127.0.0.1:{pport}")
    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    try:
        c.wait_ready()
        c.create_filter("cnt", capacity=10_000, error_rate=0.01,
                        counting=True)
        _warm(c, applier, poplog)
        # quorum-acked write: blocks until the replica acked its record
        resp = c._rpc(
            "InsertBatch",
            {"name": "cnt", "keys": [b"q1"], "min_replicas": 1,
             "min_replicas_timeout_ms": 30_000},
        )
        assert resp["acked_replicas"] == 1
        seq = resp["repl_seq"]
        assert c.last_write_seq == seq
        # the acked record IS on the replica (that is what the ack means)
        rcheck = BloomClient(f"127.0.0.1:{rport}")
        assert rcheck.include("cnt", b"q1")
        rcheck.close()
        # WAIT agrees, and per-replica gauges/histogram surfaced in obs
        assert c.wait(1, timeout_ms=5000) == 1
        fam = parse_families(render_service(psvc))
        acked = fam["tpubloom_repl_acked_seq"]
        assert any(v >= seq for v in acked.values()), acked
        assert "tpubloom_wait_barrier_seconds_count" in fam
        assert ("tpubloom_wait_blocked_current" in fam)
        h = psvc.Health({})
        assert h["status"] == "SERVING", h
        sess = h["replication"]["replicas"][0]
        assert sess["acked"] >= seq
    finally:
        c.close()
        applier.stop()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()


def test_barrier_fast_fails_without_connected_replicas(tmp_path):
    """Redis min-replicas-to-write parity: an isolated primary refuses
    quorum writes in microseconds (and Health says why), but the op DID
    apply locally (WAIT semantics — no rollback)."""
    psvc, psrv, pport, poplog = _primary(
        tmp_path, min_replicas_to_write=1
    )
    c = BloomClient(f"127.0.0.1:{pport}")
    try:
        c.wait_ready(accept_degraded=True)
        t0 = time.monotonic()
        with pytest.raises(BloomServiceError, match="NOT_ENOUGH_REPLICAS") as ei:
            c.create_filter("f", capacity=1000, error_rate=0.01)
        assert time.monotonic() - t0 < 0.5, "fast-fail path waited"
        assert ei.value.details["applied"] is True
        assert ei.value.details["connected"] == 0
        # applied locally despite the refusal — and Health is DEGRADED
        # with both the standing config gap and the fresh quorum failure
        assert "f" in c.list_filters()
        h = c.health()
        assert h["status"] == "DEGRADED"
        assert "min_replicas:0/1" in h["reasons"]
        assert "not_enough_replicas" in h["reasons"]
        # NO-OP mutating RPCs log nothing, so the quorum has nothing to
        # say about them: an exist_ok attach to the existing filter and
        # a drop of a missing one must NOT bounce with
        # NOT_ENOUGH_REPLICAS (the Ruby driver attaches on every boot)
        resp = c.create_filter("f", exist_ok=True)
        assert resp["existed"]
        assert not c.drop_filter("missing-filter")["existed"]
    finally:
        c.close()
        psrv.stop(grace=None)
        poplog.close()


def test_min_replicas_requires_an_oplog(tmp_path):
    svc = BloomService()  # no op log: nothing a replica could ever ack
    srv, port = build_server(svc, "127.0.0.1:0")
    srv.start()
    c = BloomClient(f"127.0.0.1:{port}")
    try:
        c.wait_ready()
        c.create_filter("f", capacity=1000, error_rate=0.01)
        with pytest.raises(BloomServiceError, match="NOT_ENOUGH_REPLICAS"):
            c.insert_batch("f", [b"x"], min_replicas=1)
    finally:
        c.close()
        srv.stop(grace=None)


def test_per_request_override_only_strengthens(tmp_path):
    """The server default and the request quorum compose as max():
    a request can demand MORE durability than the config, not less."""
    psvc, psrv, pport, poplog = _primary(
        tmp_path, min_replicas_to_write=1,
        # a replica's FIRST apply pays the jit compile — the barrier
        # budget must absorb it on this CPU image
        min_replicas_max_lag_ms=60_000,
    )
    c = BloomClient(f"127.0.0.1:{pport}")
    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    try:
        _wait(lambda: psvc.repl_sessions.count() == 1, msg="replica connect")
        c.wait_ready()
        c.create_filter("cnt", capacity=10_000, error_rate=0.01,
                        counting=True)
        _warm(c, applier, poplog)
        # server default (1) satisfied by the one replica
        c.insert_batch("cnt", [b"a"])
        # min_replicas=0 cannot weaken the server's 1 → still waits,
        # still succeeds
        c.insert_batch("cnt", [b"b"], min_replicas=0)
        # a stronger per-request quorum than the topology has fast-fails
        with pytest.raises(BloomServiceError, match="NOT_ENOUGH_REPLICAS") as ei:
            c.insert_batch("cnt", [b"c"], min_replicas=2)
        assert ei.value.details["needed"] == 2
        assert ei.value.details["connected"] == 1
    finally:
        c.close()
        applier.stop()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()


# -- chaos: ack loss, stream kill, slow replica ------------------------------


def test_ack_loss_blocks_write_then_reack_heals(tmp_path):
    """Arm ``repl.ack`` (frames dropped in flight): a quorum write times
    out with NOT_ENOUGH_REPLICAS even though the replica APPLIED the
    record; Wait reports the honest count under the loss; disarming
    heals through the periodic re-ack with no new records needed."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    c = BloomClient(f"127.0.0.1:{pport}")
    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    try:
        c.wait_ready()
        c.create_filter("cnt", capacity=10_000, error_rate=0.01,
                        counting=True)
        _warm(c, applier, poplog)
        c.insert_batch("cnt", [b"pre"], min_replicas=1,
                       min_replicas_timeout_ms=30_000)

        faults.arm("repl.ack", "always")
        with pytest.raises(BloomServiceError, match="NOT_ENOUGH_REPLICAS") as ei:
            c.insert_batch("cnt", [b"lost-ack"], min_replicas=1,
                           min_replicas_timeout_ms=700)
        lost_seq = ei.value.details["seq"]
        assert ei.value.details["applied"] is True
        # the replica applied it — only the ACK was lost
        assert applier.wait_for_seq(lost_seq, 30)
        rcheck = BloomClient(f"127.0.0.1:{rport}")
        assert rcheck.include("cnt", b"lost-ack")
        rcheck.close()
        # Wait is accurate under the injected loss: 0 replicas acked
        assert c.wait(1, timeout_ms=300, seq=lost_seq) == 0
        assert obs_counters.get("repl_acks_dropped") > 0
        # min_replicas_timeout_ms=0 is a PROBE: fail immediately unless
        # the quorum already acked — an explicit zero must not fall back
        # to the server's default budget
        t0 = time.monotonic()
        with pytest.raises(BloomServiceError, match="NOT_ENOUGH_REPLICAS"):
            c.insert_batch("cnt", [b"probe"], min_replicas=1,
                           min_replicas_timeout_ms=0)
        assert time.monotonic() - t0 < 0.5

        faults.reset()
        # no new writes: the periodic re-ack alone must close the gap
        _wait(
            lambda: psvc.repl_sessions.count_acked(lost_seq) == 1,
            timeout=10,
            msg="re-ack heal",
        )
        assert c.wait(1, timeout_ms=5000, seq=lost_seq) == 1
        # and quorum writes flow again
        c.insert_batch("cnt", [b"post-heal"], min_replicas=1,
                       min_replicas_timeout_ms=30_000)
    finally:
        c.close()
        applier.stop()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()


def test_ack_stream_kill_reopens_on_heartbeat(tmp_path):
    """Arm ``repl.ack_recv`` once: the primary kills the ack RPC
    mid-stream; the replica notices at its next heartbeat, re-opens the
    stream under the same session, and re-sends its cursor."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    c = BloomClient(f"127.0.0.1:{pport}")
    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    try:
        c.wait_ready()
        c.create_filter("cnt", capacity=10_000, error_rate=0.01,
                        counting=True)
        _warm(c, applier, poplog)
        before = obs_counters.get("repl_ack_stream_reopened")
        faults.arm("repl.ack_recv", "once")
        # this write's ack frame detonates the fault server-side
        try:
            c.insert_batch("cnt", [b"boom"], min_replicas=1,
                           min_replicas_timeout_ms=700)
        except BloomServiceError:
            pass  # the barrier may or may not catch the re-sent ack
        _wait(
            lambda: obs_counters.get("repl_ack_stream_reopened") > before,
            timeout=15,
            msg="ack stream reopen",
        )
        # fully healed: quorum writes succeed again
        c.insert_batch("cnt", [b"after"], min_replicas=1,
                       min_replicas_timeout_ms=30_000)
        assert c.wait(1, timeout_ms=5000) == 1
    finally:
        c.close()
        applier.stop()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()


def test_slow_replica_times_out_then_catches_up(tmp_path):
    """The ISSUE-5 satellite case end to end: a dead/slow replica makes
    the quorum write time out; once a replica reconnects and catches up,
    the SAME logical write (same rid, dedup replay) succeeds without
    double-applying."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    c = BloomClient(f"127.0.0.1:{pport}")
    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    try:
        c.wait_ready()
        c.create_filter("cnt", capacity=10_000, error_rate=0.01,
                        counting=True)
        _warm(c, applier, poplog)
        applier.stop()  # the replica goes dark
        _wait(lambda: psvc.repl_sessions.count() == 0, msg="session drop")

        with pytest.raises(BloomServiceError, match="NOT_ENOUGH_REPLICAS"):
            c.insert_batch("cnt", [b"stuck"], min_replicas=1,
                           min_replicas_timeout_ms=400)
        rid = c.last_rid

        # replica comes back and catches up
        applier2 = ReplicaApplier(
            rsvc,
            f"127.0.0.1:{pport}",
            reconnect_base=0.05,
            initial_cursor=applier.cursor,
            initial_log_id=applier.log_id,
        ).start()
        try:
            assert applier2.wait_for_seq(poplog.last_seq, 30), (
                applier2.status()
            )
            # re-drive the SAME rid: dedup answers the cached response
            # and the barrier re-waits on the ORIGINAL record — now
            # acked, so it succeeds; the count stays exactly 1
            resp = c._call_once(
                "InsertBatch",
                {"name": "cnt", "keys": [b"stuck"], "rid": rid,
                 "min_replicas": 1, "min_replicas_timeout_ms": 30_000},
            )
            assert resp["acked_replicas"] == 1
            c.delete_batch("cnt", [b"stuck"])
            assert not c.include("cnt", b"stuck"), (
                "the dedup replay double-applied the quorum write"
            )
        finally:
            applier2.stop()
    finally:
        c.close()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()


def test_ack_age_gate_counts_only_fresh_acks():
    """ISSUE 6 satellite (Redis min-replicas-max-lag parity): a replica
    that acked a seq and then went SILENT stops counting toward an
    age-gated quorum — its cursor is history, not durability."""
    from tpubloom.repl.primary import ReplicaSessions

    sess = ReplicaSessions()
    sid = sess.register("test-peer")
    sess.ack(sid, 5)
    assert sess.count_acked(5) == 1
    assert sess.count_acked(5, max_age=10.0) == 1
    time.sleep(0.15)
    # unaged counting still sees the ack; the freshness gate does not
    assert sess.count_acked(5) == 1
    assert sess.count_acked(5, max_age=0.1) == 0
    # an age-gated barrier on a stale-but-connected replica TIMES OUT
    # (it does not fast-fail: the session is connected) and reports the
    # fresh count, without a notify ever arriving — the wait re-polls
    # freshness on its own clock
    t0 = time.monotonic()
    assert sess.wait_acked(5, 1, 0.3, max_age=0.1) == 0
    assert time.monotonic() - t0 < 5.0
    # an idle re-ack of the SAME seq refreshes acked_at: fresh again
    sess.ack(sid, 5)
    assert sess.count_acked(5, max_age=0.1) == 1
    assert sess.wait_acked(5, 1, 0.3, max_age=0.1) == 1


def test_zero_lag_budget_disables_freshness_gate(tmp_path):
    """Redis ``min-replicas-max-lag 0`` = the lag check is DISABLED,
    not infinitely strict: quorum writes against a healthy replica must
    succeed (and not busy-spin the barrier into a guaranteed timeout)."""
    from tpubloom.repl.primary import ReplicaSessions

    # unit: max_age=0 counts like no gate at all
    sess = ReplicaSessions()
    sid = sess.register("test-peer")
    sess.ack(sid, 4)
    time.sleep(0.05)
    assert sess.count_acked(4, max_age=0) == 1
    assert sess.wait_acked(4, 1, 0.2, max_age=0.0) == 1

    # service: a 0 lag budget still lets a healthy quorum write through
    psvc, psrv, pport, poplog = _primary(tmp_path, min_replicas_max_lag_ms=0)
    c = BloomClient(f"127.0.0.1:{pport}")
    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    try:
        c.wait_ready()
        c.create_filter("cnt", capacity=10_000, error_rate=0.01,
                        counting=True)
        _warm(c, applier, poplog)
        resp = c._rpc(
            "InsertBatch",
            {"name": "cnt", "keys": [b"z1"], "min_replicas": 1,
             "min_replicas_timeout_ms": 30_000},
        )
        assert resp["acked_replicas"] == 1
        # no explicit wait budget: the default normally reuses the lag
        # budget, but lag 0 must fall back to the stock budget instead
        # of turning every quorum write into a 0ms instant probe
        resp = c._rpc(
            "InsertBatch",
            {"name": "cnt", "keys": [b"z2"], "min_replicas": 1},
        )
        assert resp["acked_replicas"] == 1
    finally:
        c.close()
        applier.stop()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()


def test_idle_reack_wakes_age_gated_waiter():
    """A quorum waiter blocked on FRESHNESS (seq already acked, frame
    too old) must wake on the idle re-ack that refreshes it — the
    re-ack advances no seq, so this pins the waiters-present notify."""
    from tpubloom.repl.primary import ReplicaSessions

    sess = ReplicaSessions()
    sid = sess.register("test-peer")
    sess.ack(sid, 3)
    time.sleep(0.2)  # the ack frame goes stale for a 0.15s budget
    got: list = []
    t = threading.Thread(
        target=lambda: got.append(sess.wait_acked(3, 1, 5.0, max_age=0.15)),
        daemon=True,
    )
    t.start()
    time.sleep(0.05)
    sess.ack(sid, 3)  # idle re-ack: same seq, fresh frame
    t.join(timeout=10)
    assert got == [1], got


def test_dedup_rewait_rejects_stale_acks(tmp_path):
    """The service-level customer of the freshness gate: a dedup-cache
    replay re-waits on its original record's seq — which the replica
    acked LONG AGO before going silent. Without the age gate the stale
    cursor would satisfy the quorum forever; with it the barrier answers
    NOT_ENOUGH_REPLICAS and names the stale ack, and a healed replica
    (acks flowing again) satisfies the same re-drive."""
    psvc, psrv, pport, poplog = _primary(
        tmp_path, min_replicas_max_lag_ms=300
    )
    c = BloomClient(f"127.0.0.1:{pport}")
    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    try:
        c.wait_ready()
        c.create_filter("cnt", capacity=10_000, error_rate=0.01,
                        counting=True)
        _warm(c, applier, poplog)
        resp = c._rpc(
            "InsertBatch",
            {"name": "cnt", "keys": [b"fresh1"], "min_replicas": 1,
             "min_replicas_timeout_ms": 30_000},
        )
        assert resp["acked_replicas"] == 1
        rid = c.last_rid
        # the replica stays CONNECTED but every ack frame (including the
        # 0.5s periodic idle re-acks) is dropped in flight: acked_at
        # ages past the 300ms lag budget while the acked seq stands
        faults.arm("repl.ack", "always")
        time.sleep(0.8)
        with pytest.raises(BloomServiceError, match="NOT_ENOUGH_REPLICAS") as ei:
            c._call_once(
                "InsertBatch",
                {"name": "cnt", "keys": [b"fresh1"], "rid": rid,
                 "min_replicas": 1, "min_replicas_timeout_ms": 500},
            )
        details = ei.value.details
        assert details["applied"] is True
        assert details.get("stale_acks", 0) >= 1, (
            f"the failure must name the stale ack, got {details}"
        )
        counters = psvc.metrics.snapshot()["counters"]
        assert counters.get("quorum_stale_acks", 0) >= 1, counters
        # heal: acks flow again, the periodic re-ack refreshes acked_at,
        # and the SAME rid re-drive now passes the freshness gate
        faults.reset()
        resp = c._call_once(
            "InsertBatch",
            {"name": "cnt", "keys": [b"fresh1"], "rid": rid,
             "min_replicas": 1, "min_replicas_timeout_ms": 30_000},
        )
        assert resp["acked_replicas"] == 1
        # dedup replay: applied exactly once
        c.delete_batch("cnt", [b"fresh1"])
        assert not c.include("cnt", b"fresh1")
    finally:
        c.close()
        applier.stop()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()


def test_barrier_unblocks_when_last_replica_disconnects(tmp_path):
    """A quorum made unattainable MID-WAIT (the last replica
    disconnects while the barrier is blocked) must fail immediately,
    not sleep out the whole timeout budget."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    c = BloomClient(f"127.0.0.1:{pport}")
    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    try:
        c.wait_ready()
        c.create_filter("cnt", capacity=10_000, error_rate=0.01,
                        counting=True)
        _warm(c, applier, poplog)
        faults.arm("repl.ack", "always")  # acks never arrive
        result: dict = {}

        def writer():
            try:
                c.insert_batch("cnt", [b"midwait"], min_replicas=1,
                               min_replicas_timeout_ms=20_000)
                result["outcome"] = "ok"
            except BloomServiceError as e:
                result["outcome"] = e.code

        t0 = time.monotonic()
        t = threading.Thread(target=writer, daemon=True)
        t.start()
        _wait(
            lambda: obs_counters.get_gauge("wait_blocked_current") > 0,
            msg="barrier blocked",
        )
        applier.stop()  # the quorum just became unattainable
        t.join(timeout=10)
        assert not t.is_alive(), "barrier slept out its 20s budget"
        assert result["outcome"] == "NOT_ENOUGH_REPLICAS"
        assert time.monotonic() - t0 < 10
    finally:
        c.close()
        applier.stop()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()


def test_sync_replica_blackbox_covers_quorum_applies(tmp_path):
    """ISSUE 18 satellite: an in-process sync replica given a state
    store arms the PR-16 black box there, so the post-mortem of a
    quorum write covers the REPLICA side too — the ring names the node
    (role/addr/upstream) and carries the forced ``repl.apply`` spans
    behind the ack the barrier waited on."""
    from tpubloom.obs import blackbox as bb
    from tpubloom.repl.replica import ReplicaStateStore

    psvc, psrv, pport, poplog = _primary(
        tmp_path, min_replicas_to_write=1, trace_sample=1.0
    )
    rsvc = BloomService(read_only=True)
    rsrv, rport = build_server(rsvc, "127.0.0.1:0")
    rsrv.start()
    rsvc.listen_address = f"127.0.0.1:{rport}"
    state_dir = str(tmp_path / "replica-state")
    applier = ReplicaApplier(
        rsvc,
        f"127.0.0.1:{pport}",
        reconnect_base=0.05,
        state_store=ReplicaStateStore(state_dir),
        listen_address=rsvc.listen_address,
    ).start()
    c = BloomClient(f"127.0.0.1:{pport}", trace_sample=1.0)
    try:
        assert bb.enabled(), "a state store alone must arm the black box"
        c.wait_ready()
        c.create_filter("cnt", capacity=10_000, error_rate=0.01,
                        counting=True)
        _warm(c, applier, poplog)
        # the barrier releases only after THIS replica acked the apply
        resp = c._call_once(
            "InsertBatch",
            {"name": "cnt", "keys": [b"quorum-bb"], "min_replicas": 1,
             "min_replicas_timeout_ms": 30_000,
             "trace": {"forced": True}},
        )
        assert resp["acked_replicas"] >= 1
        assert applier.wait_for_seq(poplog.last_seq, 60), applier.status()
    finally:
        c.close()
        applier.stop()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()
    node = bb.read_node(state_dir)
    assert node is not None, "replica state dir must hold a black box"
    assert node["meta"].get("role") == "replica"
    assert node["meta"].get("addr") == f"127.0.0.1:{rport}"
    assert node["meta"].get("primary") == f"127.0.0.1:{pport}"
    applies = [s for s in node["spans"] if s.get("name") == "repl.apply"]
    assert any(
        s.get("attrs", {}).get("filter") == "cnt" for s in applies
    ), "the quorum-acked apply must have spilled into the replica ring"


# -- the acceptance chaos story ----------------------------------------------

_SERVER_CHILD = """\
import sys
import jax
jax.config.update("jax_platforms", "cpu")
from tpubloom.server.service import main
main(sys.argv[1:])
"""


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _sentinel_trio(pport):
    from tpubloom.ha.sentinel import Sentinel

    sents = [
        Sentinel(
            f"127.0.0.1:{pport}",
            peers=[],
            poll_s=0.1,
            down_after_s=0.5,
            failover_cooldown_s=0.5,
        )
        for _ in range(3)
    ]
    for s in sents:
        s.peers.extend(x.address for x in sents if x is not s)
        s.quorum = 2
    for s in sents:
        s.start()
    return sents


def test_quorum_acked_survives_sigkill_without_redrive(tmp_path):
    """The ISSUE-5 acceptance scenario: batches written under
    ``min_replicas=1``; the primary (a real process) is SIGKILLed the
    instant the last quorum-acked batch returns; the sentinel quorum
    promotes the most-caught-up replica — and every acked element is
    readable on the new primary with the client's rid re-drive
    DISABLED. The quorum ack is the guarantee now, not the PR-4
    client-side patch."""
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
    script = tmp_path / "server_child.py"
    script.write_text(_SERVER_CHILD)
    proc = subprocess.Popen(
        [_sys.executable, str(script), str(port),
         "--repl-log-dir", str(tmp_path / "primary-log"),
         # black box armed in chaos mode (ISSUE 16): sample 0.0 spills
         # only slowlog-worthy work — what the post-mortem below reads
         "--trace-sample", "0.0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
    )
    boot = BloomClient(f"127.0.0.1:{port}")
    sents = []
    r1 = r2 = None
    try:
        boot.wait_ready(timeout=120)
        boot.create_filter(
            "cnt", capacity=50_000, error_rate=0.01, counting=True
        )
        r1 = _replica(tmp_path, port, name="r1log", chained=True)
        r2 = _replica(tmp_path, port, name="r2log", chained=True)
        sents = _sentinel_trio(port)
        _wait(
            lambda: len(sents[0].handle_Topology({})["replicas"]) == 2,
            msg="replica discovery",
        )
        # warm the replicas' jit outside any barrier window (the client
        # tracks the subprocess primary's log seq via repl_seq)
        boot.insert_batch("cnt", [b"warmup"])
        for r in (r1, r2):
            assert r[3].wait_for_seq(boot.last_write_seq, 60), r[3].status()

        batches = [
            [b"acc-%03d-%03d" % (i, j) for j in range(20)] for i in range(6)
        ]
        for keys in batches:
            boot.insert_batch(
                "cnt", keys, min_replicas=1, min_replicas_timeout_ms=60_000
            )
        last_rid = boot.last_rid
        # the last quorum-acked batch JUST returned: kill the primary NOW
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)

        # post-mortem (ISSUE 16): the killed primary's mmap'd black box
        # must carry its lifecycle AND the final quorum-acked batch's
        # spilled spans — the write it acked the instant it died
        from tpubloom.obs import blackbox as bb

        node = bb.read_node(str(tmp_path / "primary-log"))
        assert node is not None, "SIGKILL must leave a readable black box"
        assert node["meta"].get("role") == "primary"
        assert "boot" in [e["kind"] for e in node["events"]]
        assert last_rid in {s.get("rid") for s in node["spans"]}, (
            "the final quorum-acked rid's span must have spilled"
        )

        _wait(
            lambda: any(s.failovers for s in sents),
            timeout=90,
            msg="sentinel failover",
        )
        # fetch_topology answers from the FIRST sentinel that responds,
        # which may not be the election leader — its view flips only
        # when the leader's AnnounceTopology lands, so poll for the
        # new primary instead of asserting on one snapshot
        topo = None

        def _new_primary():
            nonlocal topo
            topo = fetch_topology([s.address for s in sents])
            return (
                topo is not None
                and topo["primary"] != f"127.0.0.1:{port}"
            )

        _wait(_new_primary, timeout=30, msg="topology announce")

        # re-drive DISABLED: a fresh client only READS the new primary —
        # every quorum-acked element must already be there, because the
        # ack proves it reached a replica and the sentinel's
        # most-caught-up election (highest cursor) picks a winner whose
        # log contains every record ANY replica acked
        fresh = BloomClient(topo["primary"], max_retries=0)
        all_keys = [k for b in batches for k in b]
        hits = fresh.include_batch("cnt", all_keys)
        assert hits.all(), (
            f"{int((~hits).sum())} quorum-acked key(s) missing on the "
            f"promotion winner"
        )
        fresh.close()
    finally:
        if proc.poll() is None:
            proc.kill()
        for s in sents:
            s.stop()
        for r in (r1, r2):
            if r is None:
                continue
            svc, srv, _, app = r
            if svc.replica_applier is not None:
                svc.replica_applier.stop()
            app.stop()
            srv.stop(grace=None)
            if svc.oplog is not None:
                svc.oplog.close()
        boot.close()


def test_async_control_loses_unreplicated_write(tmp_path):
    """The control run the acceptance criterion demands: with the
    barrier OFF (min_replicas=0) an acked write that never replicated is
    GONE after a primary crash + promotion — proving the quorum ack, not
    luck, is what the sigkill test's guarantee rests on. And with the
    barrier ON in the same topology, the write is refused rather than
    falsely acked."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    c = BloomClient(f"127.0.0.1:{pport}")
    rsvc, rsrv, rport, applier = _replica(
        tmp_path, pport, name="rlog", chained=True
    )
    try:
        c.wait_ready()
        c.create_filter("cnt", capacity=10_000, error_rate=0.001,
                        counting=True)
        _warm(c, applier, poplog)
        b0 = [b"dur-%03d" % i for i in range(20)]
        b1 = [b"gone-%03d" % i for i in range(20)]
        # B0: quorum-acked — provably on the replica
        c.insert_batch("cnt", b0, min_replicas=1,
                       min_replicas_timeout_ms=30_000)
        # the replica goes deaf BEFORE B1
        applier.stop()
        _wait(lambda: psvc.repl_sessions.count() == 0, msg="session drop")
        # B1: async ack (min_replicas=0) — the primary alone has it
        c.insert_batch("cnt", b1)
        # barrier honesty: the same write under min_replicas=1 is
        # REFUSED (fast-fail), not falsely acked
        with pytest.raises(BloomServiceError, match="NOT_ENOUGH_REPLICAS"):
            c.insert_batch("cnt", [b"refused"], min_replicas=1,
                           min_replicas_timeout_ms=400)

        # primary "crashes"; the replica is promoted
        psrv.stop(grace=None)
        rc = BloomClient(f"127.0.0.1:{rport}")
        resp = rc.promote()
        assert resp["ok"] and not resp["already_primary"]
        hits0 = rc.include_batch("cnt", b0)
        assert hits0.all(), "quorum-acked batch lost despite the barrier"
        hits1 = rc.include_batch("cnt", b1)
        assert not hits1.all(), (
            "the async-acked batch survived — the control cannot "
            "distinguish the barrier from plain replication luck"
        )
        rc.close()
    finally:
        c.close()
        if rsvc.replica_applier is not None:
            rsvc.replica_applier.stop()
        applier.stop()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()
        if rsvc.oplog is not None:
            rsvc.oplog.close()


def test_wait_smoke():
    """benchmarks/wait_smoke.py runs in tier-1 so the durability surface
    cannot silently rot (and CI runs it standalone)."""
    import importlib
    import sys

    bench_dir = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, os.path.abspath(bench_dir))
    try:
        wait_smoke = importlib.import_module("wait_smoke")
        result = wait_smoke.run_smoke()
    finally:
        sys.path.pop(0)
    assert result["wait_nreplicas"] == 2
    assert set(result["mean_ms"]) == {"0", "1", "2"}
    assert set(result["overhead_ms"]) == {"1", "2"}
