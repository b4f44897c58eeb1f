"""High-availability suite (ISSUE 4).

Layers covered:

* topology primitives — CRC-checked epoch store, op-log seq seeding,
  identity alias (replid2 parity), full-resync reset;
* promotion — bare replica (fresh log adoption) and chained replica
  (cheap: the local log IS the adopted log), epoch bump + persistence,
  idempotence, STALE_EPOCH fencing of old-epoch promotions/writes;
* ``ReplicaOf`` — survivor re-pointing with alias partial resync,
  ``NO ONE`` == promote, live-primary demotion;
* chained replication — re-append in the upstream seq space, downstream
  ``ReplStream`` serving, exactly-once across the chain;
* replica cursor persistence — a replica restart partial-resyncs from
  local checkpoints + ``repl_cursor.json`` instead of full-resyncing;
* batched stream frames — zlib-coalesced records behind the negotiated
  capability, same exactly-once guarantees;
* sentinel — quorum SDOWN→ODOWN vote, most-caught-up promotion,
  survivor re-pointing, stale-primary fencing, no-quorum safety;
* topology-aware client — sentinel resolution, failover redirect,
  STALE_EPOCH refresh;
* the acceptance chaos story — SIGKILL the primary under concurrent
  client load, sentinel failover, client redirect, counting-filter
  proof of zero lost / zero doubled acknowledged writes, and fencing of
  the restarted stale primary (``test_failover_sigkill_acceptance``).
"""

import os
import threading
import time

import numpy as np
import pytest

from tpubloom import checkpoint as ckpt
from tpubloom import faults
from tpubloom.ha import EpochStore, Topology
from tpubloom.ha.sentinel import Sentinel
from tpubloom.obs import counters as obs_counters
from tpubloom.repl import (
    OpLog,
    ReplicaApplier,
    ReplicaStateStore,
    bootstrap_from_local,
)
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


def _wait(pred, timeout=30.0, poll=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    raise AssertionError(f"timed out waiting for {msg}")


def _primary(tmp_path, name="plog", sink=None, **kwargs):
    oplog = OpLog(str(tmp_path / name))
    svc = BloomService(
        sink_factory=(lambda config: ckpt.FileSink(sink)) if sink else None,
        oplog=oplog,
        **kwargs,
    )
    srv, port = build_server(svc, "127.0.0.1:0")
    srv.start()
    svc.listen_address = f"127.0.0.1:{port}"
    return svc, srv, port, oplog


def _replica(tmp_path, upstream_port, name=None, chained=False, **svc_kwargs):
    oplog = OpLog(str(tmp_path / name)) if chained else None
    svc = BloomService(oplog=oplog, read_only=True, **svc_kwargs)
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


# -- topology primitives -----------------------------------------------------


def test_epoch_store_roundtrip_and_corruption(tmp_path):
    store = EpochStore(str(tmp_path))
    assert store.load() == 0
    store.store(5)
    assert store.load() == 5
    assert EpochStore(str(tmp_path)).load() == 5  # fresh reader
    with open(store.path, "a") as f:
        f.write("rot")
    # corrupt reads as 0 — the fence-me-harder direction, never a crash
    assert store.load() == 0


def test_topology_adopt_epoch_discipline():
    topo = Topology(epoch=3, primary="a:1", replicas=["b:2"])
    assert not topo.adopt(Topology(epoch=3, primary="c:3"))  # same epoch
    assert not topo.adopt(Topology(epoch=2, primary="c:3"))  # older
    assert topo.adopt(Topology(epoch=4, primary="c:3", replicas=["a:1"]))
    assert topo.primary == "c:3" and topo.epoch == 4


def test_oplog_seed_alias_and_reset(tmp_path):
    d = str(tmp_path / "log")
    lg = OpLog(d, start_seq=10)
    assert lg.last_seq == 10 and lg.append("Clear", {"name": "f"}) == 11
    lg.set_alias("old-primary-id", 10)
    lg.close()

    lg2 = OpLog(d)  # alias persists a restart
    assert lg2.alias_id == "old-primary-id" and lg2.alias_upto == 10
    # exactly-caught-up survivor resumes through the alias...
    assert lg2.resumable(10, "old-primary-id")
    # ...but a cursor BELOW the seed has no records to stream from here
    assert not lg2.resumable(9, "old-primary-id")
    # a cursor past the alias window (divergence risk) must full-resync
    lg2.set_alias("old-primary-id", 10)
    assert not lg2.resumable(11, "old-primary-id")
    assert lg2.resumable(11, lg2.log_id)

    old_id = lg2.log_id
    lg2.reset_to(40)  # full-resync reset: wipe + reseed + new identity
    assert lg2.last_seq == 40 and lg2.log_id != old_id
    assert lg2.alias_id is None
    assert lg2.append("Clear", {"name": "f"}) == 41
    assert not lg2.resumable(41, old_id)
    lg2.close()


def test_oplog_append_record_verbatim_and_gap(tmp_path):
    lg = OpLog(str(tmp_path / "log"), start_seq=5)
    rec = {"seq": 6, "method": "Clear", "rid": "r", "req": {"name": "f"},
           "ts": 1.0}
    assert lg.append_record(rec)
    assert not lg.append_record(rec)  # dup (partial-resync overlap)
    got = list(lg.read_from(5))
    assert got == [rec]
    with pytest.raises(ValueError, match="gap"):
        lg.append_record({**rec, "seq": 8})
    lg.close()


# -- promotion ---------------------------------------------------------------


def test_promote_bare_replica_adopts_fresh_log(tmp_path):
    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    keys = [b"p%015d" % i for i in range(100)]
    pc.create_filter("cnt", capacity=10_000, error_rate=0.01, counting=True)
    pc.insert_batch("cnt", keys)

    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    rc = BloomClient(f"127.0.0.1:{rport}")
    try:
        assert applier.wait_for_seq(poplog.last_seq, 30), applier.status()
        with pytest.raises(BloomServiceError, match="NO_LOG_DIR"):
            rc.promote()  # bare replica needs a log dir to adopt
        adopt_dir = str(tmp_path / "adopted")
        resp = rc.promote(repl_log_dir=adopt_dir)
        assert resp["epoch"] == 1 and not resp["already_primary"]
        assert resp["adopted_seq"] == poplog.last_seq
        # promoted: accepts writes, logs them in the adopted seq space
        h = rc.health()
        assert h["role"] == "primary" and h["epoch"] == 1
        rc.insert_batch("cnt", [b"after"])
        assert rsvc.oplog.last_seq == poplog.last_seq + 1
        assert rsvc.oplog.directory == adopt_dir
        # the epoch persisted beside the adopted log
        assert EpochStore(adopt_dir).load() == 1
        # idempotent re-promote; stale pinned epoch rejected (raw call:
        # the stock client would heal by adopting the advertised epoch)
        assert rc.promote()["already_primary"]
        with pytest.raises(BloomServiceError, match="STALE_EPOCH"):
            rc._call_once("Promote", {"epoch": 0})
        # a fresh-log restart of the promoted node replays its manifest:
        # the pre-promotion keys must have been manifest-seeded
        manifest = rsvc._manifest_read()
        assert manifest is not None and "cnt" in manifest
    finally:
        rc.close()
        pc.close()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()


def test_chained_replica_serves_downstream_and_promotes_cheap(tmp_path):
    """Chain primary→mid→leaf; every link sees every write exactly once;
    promoting the mid node costs nothing (its log IS the adopted log)
    and the old primary's OTHER replica partial-resyncs via the alias."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    keys = [b"c%015d" % i for i in range(200)]
    pc.create_filter("cnt", capacity=20_000, error_rate=0.01, counting=True)
    pc.insert_batch("cnt", keys)

    mid_svc, mid_srv, mid_port, mid_app = _replica(
        tmp_path, pport, name="midlog", chained=True
    )
    sib_svc, sib_srv, sib_port, sib_app = _replica(tmp_path, pport)
    # leaf chains off the MID node (its ReplStream serves downstream)
    leaf_svc, leaf_srv, leaf_port, leaf_app = _replica(tmp_path, mid_port)
    lc = BloomClient(f"127.0.0.1:{leaf_port}")
    mc = BloomClient(f"127.0.0.1:{mid_port}")
    try:
        assert mid_app.wait_for_seq(poplog.last_seq, 30), mid_app.status()
        # the chained log lives in the upstream seq space (the initial
        # full resync seeds it at the resync cursor; LIVE records are
        # re-appended verbatim)
        assert mid_svc.oplog.last_seq == poplog.last_seq
        before_re = obs_counters.get("repl_records_reappended")
        live = [b"live-%07d" % i for i in range(30)]
        pc.insert_batch("cnt", live)
        assert mid_app.wait_for_seq(poplog.last_seq, 30)
        assert obs_counters.get("repl_records_reappended") > before_re
        assert leaf_app.wait_for_seq(mid_svc.oplog.last_seq, 30)
        assert lc.include_batch("cnt", keys).all()
        assert lc.include_batch("cnt", live).all()
        assert sib_app.wait_for_seq(poplog.last_seq, 30)

        # promote mid; survivors re-point; alias gives partial resync
        resp = mc.promote()
        assert not resp["already_primary"] and resp["epoch"] == 1
        sc = BloomClient(f"127.0.0.1:{sib_port}")
        sc._rpc("ReplicaOf", {"primary": f"127.0.0.1:{mid_port}",
                              "epoch": 1})
        new_sib = sib_svc.replica_applier
        assert new_sib is not sib_app
        mc.insert_batch("cnt", [b"post-promote"])
        assert new_sib.wait_for_seq(mid_svc.oplog.last_seq, 30), (
            new_sib.status()
        )
        assert new_sib.partial_syncs >= 1 and new_sib.full_syncs == 0, (
            "survivor paid a full resync despite the identity alias"
        )
        # the mid→leaf link just keeps streaming (same log identity)
        assert leaf_app.wait_for_seq(mid_svc.oplog.last_seq, 30)
        assert lc.include("cnt", b"post-promote")
        assert sc.include("cnt", b"post-promote")
        # exactly-once along the whole (re-shaped) topology
        mc.delete_batch("cnt", keys)
        assert new_sib.wait_for_seq(mid_svc.oplog.last_seq, 30)
        assert leaf_app.wait_for_seq(mid_svc.oplog.last_seq, 30)
        for cl in (mc, sc, lc):
            assert not cl.include_batch("cnt", keys).any(), (
                "double-applied records after promotion"
            )
        sc.close()
    finally:
        for app in (leaf_app, mid_app, sib_app, sib_svc.replica_applier):
            if app is not None:
                app.stop()
        for cl in (lc, mc, pc):
            cl.close()
        for srv in (leaf_srv, sib_srv, mid_srv, psrv):
            srv.stop(grace=None)
        poplog.close()
        for svc in (mid_svc, leaf_svc):
            if svc.oplog is not None:
                svc.oplog.close()


def test_replicaof_no_one_promotes_and_demotion_fences_writes(tmp_path):
    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    pc.create_filter("f", capacity=1000, error_rate=0.01)
    pc.insert_batch("f", [b"seed"])

    rsvc, rsrv, rport, applier = _replica(
        tmp_path, pport, name="rlog", chained=True
    )
    rc = BloomClient(f"127.0.0.1:{rport}")
    try:
        assert applier.wait_for_seq(poplog.last_seq, 30)
        # REPLICAOF NO ONE == promote
        resp = rc.replica_of("NO ONE")
        assert resp["ok"] and rc.health()["role"] == "primary"
        # demote the OLD primary onto the new one: writes fence instantly
        resp = pc.replica_of(f"127.0.0.1:{rport}", epoch=rsvc.epoch)
        assert resp["was_primary"]
        fresh = BloomClient(f"127.0.0.1:{pport}", max_retries=0)
        with pytest.raises(BloomServiceError, match="READONLY"):
            fresh._call_once("InsertBatch", {"name": "f", "keys": [b"x"]})
        fresh.close()
        # and it syncs content from the new primary
        rc.insert_batch("f", [b"from-new-primary"])
        demoted = psvc.replica_applier
        assert demoted is not None
        assert demoted.wait_for_seq(rsvc.oplog.last_seq, 30), (
            demoted.status()
        )
        check = BloomClient(f"127.0.0.1:{pport}")
        assert check.include("f", b"from-new-primary")
        check.close()
        # stale ReplicaOf (older epoch) is rejected (raw call: the stock
        # client would heal by adopting the advertised epoch)
        with pytest.raises(BloomServiceError, match="STALE_EPOCH"):
            pc._call_once(
                "ReplicaOf", {"primary": "127.0.0.1:1", "epoch": 0}
            )
    finally:
        if psvc.replica_applier is not None:
            psvc.replica_applier.stop()
        rc.close()
        pc.close()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()
        if rsvc.oplog is not None:
            rsvc.oplog.close()


def test_promotion_during_partial_resync_stays_exactly_once(tmp_path):
    """Kill the stream mid-batch; promote WHILE the link is lost (the
    reconnect-in-flight case): the promoted node must adopt exactly what
    it applied, and counting counts prove nothing doubled or vanished."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    keys = [b"m%015d" % i for i in range(150)]
    pc.create_filter("cnt", capacity=20_000, error_rate=0.01, counting=True)
    pc.insert_batch("cnt", keys)

    rsvc, rsrv, rport, applier = _replica(
        tmp_path, pport, name="rlog", chained=True
    )
    rc = BloomClient(f"127.0.0.1:{rport}")
    try:
        assert applier.wait_for_seq(poplog.last_seq, 30)
        faults.arm("repl.stream_send", "once")
        pc.insert_batch("cnt", keys[:50])  # count -> 2 for those
        _wait(
            lambda: applier.link in ("lost", "connecting")
            or applier.partial_syncs > 0,
            msg="stream break",
        )
        resp = rc.promote()  # mid-resync promotion
        assert resp["ok"]
        # whatever the applier had applied is the adopted history; the
        # client now re-drives the batch against the new primary with
        # the SAME rid — dedup/seq-gating must keep counts exact.
        applied_second = rsvc.oplog.last_seq >= poplog.last_seq
        if not applied_second:
            rc.insert_batch("cnt", keys[:50])
        rc.delete_batch("cnt", keys[:50])  # 2 - 1 = 1
        rc.delete_batch("cnt", keys)       # 1 - 1 = 0
        assert not rc.include_batch("cnt", keys).any(), (
            "promotion mid-resync lost or doubled records"
        )
    finally:
        rc.close()
        pc.close()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()
        if rsvc.oplog is not None:
            rsvc.oplog.close()


# -- replica cursor persistence (satellite) ----------------------------------


def test_replica_restart_partial_resyncs_from_local_state(tmp_path):
    """PR-3 follow-up closed: a replica with local checkpoints + the
    CRC-checked ``repl_cursor.json`` restarts into a PARTIAL resync —
    no full snapshot transfer — and stays exactly-once."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    keys = [b"r%015d" % i for i in range(120)]
    pc.create_filter("cnt", capacity=20_000, error_rate=0.01, counting=True)
    pc.insert_batch("cnt", keys)

    state_dir = str(tmp_path / "replica-state")
    sink_dir = str(tmp_path / "replica-ckpt")
    store = ReplicaStateStore(state_dir)

    def make_replica_service():
        svc = BloomService(
            sink_factory=lambda config: ckpt.FileSink(sink_dir),
            read_only=True,
        )
        svc._manifest_dir = state_dir
        svc.replica_state_store = store
        return svc

    rsvc = make_replica_service()
    rsrv, rport = build_server(rsvc, "127.0.0.1:0")
    rsrv.start()
    applier = ReplicaApplier(
        rsvc, f"127.0.0.1:{pport}", reconnect_base=0.05, state_store=store
    ).start()
    try:
        assert applier.wait_for_seq(poplog.last_seq, 30), applier.status()
        assert applier.full_syncs == 1
        # checkpoint locally so restart has state to restore
        rsvc.Checkpoint({"name": "cnt", "wait": True})
        applier.stop()
        rsrv.stop(grace=None)
        assert store.load() is not None  # cursor persisted on stop

        # writes continue while the replica is down
        pc.insert_batch("cnt", [b"while-down"])

        # "restart": fresh service, same sink/manifest/cursor state
        rsvc2 = make_replica_service()
        cursor, log_id = bootstrap_from_local(rsvc2, store)
        assert cursor is not None and log_id == poplog.log_id
        rsrv2, rport2 = build_server(rsvc2, "127.0.0.1:0")
        rsrv2.start()
        applier2 = ReplicaApplier(
            rsvc2,
            f"127.0.0.1:{pport}",
            reconnect_base=0.05,
            state_store=store,
            initial_cursor=cursor,
            initial_log_id=log_id,
        ).start()
        try:
            assert applier2.wait_for_seq(poplog.last_seq, 30), (
                applier2.status()
            )
            assert applier2.full_syncs == 0, (
                "restart paid a full resync despite local state"
            )
            assert applier2.partial_syncs == 1
            rc = BloomClient(f"127.0.0.1:{rport2}")
            assert rc.include("cnt", b"while-down")
            # exactly-once across restart + partial resync
            pc.delete_batch("cnt", keys)
            assert applier2.wait_for_seq(poplog.last_seq, 30)
            assert not rc.include_batch("cnt", keys).any(), (
                "records double-applied across the replica restart"
            )
            rc.close()
        finally:
            applier2.stop()
            rsrv2.stop(grace=None)
    finally:
        pc.close()
        psrv.stop(grace=None)
        poplog.close()


def test_replica_cursor_file_corruption_forces_full_resync(tmp_path):
    store = ReplicaStateStore(str(tmp_path))
    store.store(42, "someid")
    assert store.load() == {"cursor": 42, "log_id": "someid"}
    with open(store.path, "a") as f:
        f.write("zzz")
    assert store.load() is None  # corrupt -> no cursor -> full resync


# -- batched stream frames (satellite) ---------------------------------------


def test_batched_stream_frames_roundtrip_exactly_once(tmp_path):
    """--repl-batch-bytes + the negotiated capability coalesce a record
    tail into zlib frames; content and exactly-once semantics
    unchanged. The tail is built deterministically: sync, disconnect,
    accumulate 64 records, reconnect with the carried cursor (partial
    resync streams the whole backlog at once)."""
    oplog = OpLog(str(tmp_path / "plog"))
    psvc = BloomService(oplog=oplog, repl_batch_bytes=2048)
    psrv, pport = build_server(psvc, "127.0.0.1:0")
    psrv.start()
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    keys = [b"b%015d" % i for i in range(64)]
    pc.create_filter("cnt", capacity=20_000, error_rate=0.01, counting=True)

    rsvc, rsrv, rport, applier = _replica(tmp_path, pport)
    rc = BloomClient(f"127.0.0.1:{rport}")
    try:
        assert applier.wait_for_seq(oplog.last_seq, 30), applier.status()
        applier.stop()  # disconnect; the backlog accumulates
        for k in keys:
            pc.insert_batch("cnt", [k])

        before = obs_counters.get("repl_stream_batched_frames")
        applier2 = ReplicaApplier(
            rsvc,
            f"127.0.0.1:{pport}",
            reconnect_base=0.05,
            initial_cursor=applier.cursor,
            initial_log_id=applier.log_id,
        ).start()
        try:
            assert applier2.wait_for_seq(oplog.last_seq, 30), (
                applier2.status()
            )
            assert applier2.partial_syncs == 1
            assert obs_counters.get("repl_stream_batched_frames") > before
            assert obs_counters.get("repl_batched_frames_received") > 0
            # compression actually compressed (repeated msgpack keys)
            raw = obs_counters.get("repl_stream_batched_bytes_raw")
            wire = obs_counters.get("repl_stream_batched_bytes_wire")
            assert 0 < wire < raw
            assert rc.include_batch("cnt", keys).all()
            pc.delete_batch("cnt", keys)
            assert applier2.wait_for_seq(oplog.last_seq, 30)
            assert not rc.include_batch("cnt", keys).any(), (
                "batched frames double-applied records"
            )
        finally:
            applier2.stop()
    finally:
        rc.close()
        pc.close()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        oplog.close()


# -- sentinel ----------------------------------------------------------------


def _sentinel_trio(pport, **kwargs):
    defaults = dict(poll_s=0.1, down_after_s=0.5, failover_cooldown_s=0.5)
    defaults.update(kwargs)
    sents = [
        Sentinel(f"127.0.0.1:{pport}", peers=[], **defaults) for _ in range(3)
    ]
    for s in sents:
        s.peers.extend(x.address for x in sents if x is not s)
        s.quorum = 2
    for s in sents:
        s.start()
    return sents


def test_sentinel_quorum_failover_promotes_most_caught_up(tmp_path):
    """The coordinator story end to end, in-process: SDOWN→ODOWN vote,
    most-caught-up pick, survivor re-point, client redirect via
    sentinels, and fencing of the restarted stale primary."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    keys = [b"q%015d" % i for i in range(200)]
    pc.create_filter("cnt", capacity=20_000, error_rate=0.01, counting=True)
    pc.insert_batch("cnt", keys)

    r1 = _replica(tmp_path, pport, name="r1log", chained=True)
    r2 = _replica(tmp_path, pport, name="r2log", chained=True)
    sents = _sentinel_trio(pport)
    try:
        for _, _, _, app in (r1, r2):
            assert app.wait_for_seq(poplog.last_seq, 30)
        _wait(
            lambda: len(sents[0].handle_Topology({})["replicas"]) == 2,
            msg="replica discovery",
        )
        # make r2 lag so the pick is meaningful
        r2[3].stop()
        pc.insert_batch("cnt", [b"fresh-%d" % i for i in range(40)])
        assert r1[3].wait_for_seq(poplog.last_seq, 30)

        psrv.stop(grace=None)  # the primary dies
        _wait(
            lambda: any(s.failovers for s in sents),
            timeout=25,
            msg="failover",
        )
        time.sleep(1.5)  # would-be dueling second election window
        assert sum(s.failovers for s in sents) == 1
        leader = next(s for s in sents if s.failovers)
        topo = leader.handle_Topology({})
        assert topo["primary"] == r1[0].listen_address, (
            "sentinel promoted a lagging replica over the caught-up one"
        )

        # topology-aware client: resolves + writes against the new primary
        c = BloomClient(
            sentinels=[s.address for s in sents],
            max_retries=3,
            backoff_base=0.05,
        )
        c.insert_batch("cnt", [b"post-failover"])
        assert c.address == r1[0].listen_address
        assert c.epoch == topo["epoch"]

        # the lagging survivor was re-pointed and catches up
        new_app = r2[0].replica_applier
        assert new_app is not None and new_app is not r2[3]
        assert new_app.wait_for_seq(r1[0].oplog.last_seq, 30), (
            new_app.status()
        )
        rc2 = BloomClient(f"127.0.0.1:{r2[2]}")
        assert rc2.include("cnt", b"post-failover")
        rc2.close()

        # fencing: the old primary restarts (stale epoch) on its old port
        back_oplog = OpLog(psvc.oplog.directory)
        back_svc = BloomService(oplog=back_oplog)
        back_svc.replay_oplog()
        back_svc.listen_address = f"127.0.0.1:{pport}"
        back_srv, back_port = build_server(back_svc, f"127.0.0.1:{pport}")
        assert back_port == pport
        back_srv.start()
        assert not back_svc.read_only and back_svc.epoch == 0
        _wait(lambda: back_svc.read_only, timeout=20, msg="fencing")
        h = BloomClient(f"127.0.0.1:{pport}").health()
        assert h["role"] == "replica" and h["epoch"] == topo["epoch"]
        assert back_svc.replica_applier.wait_for_seq(
            r1[0].oplog.last_seq, 30
        ), back_svc.replica_applier.status()
        fc = BloomClient(f"127.0.0.1:{pport}")
        assert fc.include("cnt", b"post-failover")
        fc.close()
        back_svc.replica_applier.stop()
        back_srv.stop(grace=None)
        back_oplog.close()
        c.close()
    finally:
        for s in sents:
            s.stop()
        for svc, srv, _, app in (r1, r2):
            if svc.replica_applier is not None:
                svc.replica_applier.stop()
            app.stop()
            srv.stop(grace=None)
            if svc.oplog is not None:
                svc.oplog.close()
        pc.close()
        poplog.close()


def test_sentinel_without_quorum_never_fails_over(tmp_path):
    """One vote of a required two must NOT promote — a partitioned
    minority sentinel cannot split-brain the deployment."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    r1 = _replica(tmp_path, pport, name="r1log", chained=True)
    lone = Sentinel(
        f"127.0.0.1:{pport}",
        peers=["127.0.0.1:1"],  # unreachable peer
        quorum=2,
        poll_s=0.1,
        down_after_s=0.3,
        failover_cooldown_s=0.3,
    ).start()
    try:
        _wait(
            lambda: len(lone.handle_Topology({})["replicas"]) == 1,
            msg="discovery",
        )
        psrv.stop(grace=None)
        time.sleep(3.0)  # several election attempts' worth
        assert lone.failovers == 0
        assert r1[0].read_only, "replica was promoted without quorum"
        assert lone.handle_Topology({})["primary"] == f"127.0.0.1:{pport}"
    finally:
        lone.stop()
        r1[3].stop()
        r1[1].stop(grace=None)
        if r1[0].oplog is not None:
            r1[0].oplog.close()
        pc.close()
        poplog.close()


def test_sentinel_state_persists_across_restart(tmp_path):
    """ISSUE-5 satellite (closes the PR-4 follow-up): with --state-dir,
    a sentinel restart remembers the failover history — the adopted
    topology epoch/primary AND the one-vote-per-epoch discipline — so a
    full-quorum restart cannot re-grant spent epochs or resume watching
    the pre-failover primary."""
    state = str(tmp_path / "sentinel-state")
    s = Sentinel("127.0.0.1:1", peers=[], quorum=2, state_dir=state)
    # a completed failover announced by a peer leader
    s.handle_AnnounceTopology(
        {"epoch": 7, "primary": "127.0.0.1:9", "replicas": ["127.0.0.1:8"],
         "fenced": "127.0.0.1:1"}
    )
    # and a vote granted in a later election
    s._sdown = True
    assert s.handle_VoteDown({"epoch": 8, "primary": "127.0.0.1:9"})["granted"]

    # "restart": a fresh Sentinel over the same state dir
    s2 = Sentinel("127.0.0.1:1", peers=[], quorum=2, state_dir=state)
    topo = s2.handle_Topology({})
    assert topo["epoch"] == 7 and topo["primary"] == "127.0.0.1:9"
    assert "127.0.0.1:8" in topo["replicas"]
    # the fenced-primary watchlist survives too — a stale primary that
    # reappears AFTER the restart must still get demoted on sight
    assert "127.0.0.1:1" in s2._fence_watch
    # the spent vote survives: epoch 8 cannot be granted twice...
    s2._sdown = True
    assert not s2.handle_VoteDown(
        {"epoch": 8, "primary": "127.0.0.1:9"}
    )["granted"]
    # ...but a genuinely newer epoch can
    assert s2.handle_VoteDown({"epoch": 9, "primary": "127.0.0.1:9"})["granted"]

    # corruption reads as absent — fall back to --watch, never crash
    store_path = s2._state_store.path
    with open(store_path, "a") as f:
        f.write("rot")
    s3 = Sentinel("127.0.0.1:1", peers=[], quorum=2, state_dir=state)
    assert s3.handle_Topology({})["primary"] == "127.0.0.1:1"
    assert s3._last_vote_epoch == 0


def test_sentinel_vote_rules():
    s = Sentinel("127.0.0.1:1", peers=[], quorum=2)
    # not sdown -> no grant
    resp = s.handle_VoteDown({"epoch": 1, "primary": "127.0.0.1:1"})
    assert not resp["granted"]
    s._sdown = True
    # wrong primary -> no grant
    assert not s.handle_VoteDown(
        {"epoch": 1, "primary": "elsewhere:9"}
    )["granted"]
    # proper request -> granted, and the epoch is spent (vote once)
    assert s.handle_VoteDown({"epoch": 1, "primary": "127.0.0.1:1"})["granted"]
    assert not s.handle_VoteDown(
        {"epoch": 1, "primary": "127.0.0.1:1"}
    )["granted"]
    # a newer epoch is grantable again
    assert s.handle_VoteDown({"epoch": 2, "primary": "127.0.0.1:1"})["granted"]


# -- topology-aware client ---------------------------------------------------


def test_client_static_topology_and_stale_epoch_recovery(tmp_path):
    psvc, psrv, pport, poplog = _primary(tmp_path)
    try:
        psvc.adopt_epoch(3)
        # a client under an OLD epoch view: first write bounces with
        # STALE_EPOCH, the client adopts the server's epoch and retries
        c = BloomClient(
            topology={
                "epoch": 1,
                "primary": f"127.0.0.1:{pport}",
                "replicas": [],
            }
        )
        c.wait_ready()
        before = obs_counters.get("client_topology_refreshes")
        c.create_filter("t", capacity=1000, error_rate=0.01)
        c.insert_batch("t", [b"x"])
        assert c.epoch == 3
        assert c.include("t", b"x")
        assert (
            psvc.metrics.snapshot()["counters"]["stale_epoch_rejected"] >= 1
        )
        assert obs_counters.get("client_topology_refreshes") == before
        c.close()
    finally:
        psrv.stop(grace=None)
        poplog.close()


def test_fetch_topology_none_when_unreachable():
    assert fetch_topology(["127.0.0.1:1"], timeout=0.3) is None


# -- CLI ---------------------------------------------------------------------


def test_promote_cli_subcommand(tmp_path, capsys):
    from tpubloom.server.service import main as server_main

    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    pc.create_filter("f", capacity=1000, error_rate=0.01)
    rsvc, rsrv, rport, applier = _replica(
        tmp_path, pport, name="rlog", chained=True
    )
    try:
        assert applier.wait_for_seq(poplog.last_seq, 30)
        with pytest.raises(SystemExit) as e:
            server_main(["promote", f"127.0.0.1:{rport}"])
        assert e.value.code == 0
        out = capsys.readouterr().out
        assert '"epoch": 1' in out
        assert rsvc.read_only is False
    finally:
        pc.close()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()
        if rsvc.oplog is not None:
            rsvc.oplog.close()


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


def test_failover_sigkill_acceptance(tmp_path):
    """The ISSUE-4 acceptance scenario: SIGKILL the primary (a real
    process) under concurrent client load → the sentinel quorum promotes
    the most-caught-up replica → the surviving replica re-points via
    ReplicaOf → the client completes every batch through its sentinel
    view — and counting-filter counts prove zero lost / zero doubled
    acknowledged writes. The restarted old primary (stale epoch) is
    fenced back to replica."""
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
    plog = tmp_path / "primary-log"
    script = tmp_path / "server_child.py"
    script.write_text(_SERVER_CHILD)
    child_args = [
        _sys.executable, str(script), str(port),
        "--repl-log-dir", str(plog),
        # black box armed in chaos mode (ISSUE 16): sample 0.0 means
        # only slowlog-worthy/forced work spills — the worst case the
        # post-mortem below must still decode after the SIGKILL
        "--trace-sample", "0.0",
    ]
    proc = subprocess.Popen(
        child_args,
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

        client = BloomClient(
            sentinels=[s.address for s in sents],
            max_retries=8,
            backoff_base=0.1,
            backoff_max=1.0,
            breaker_threshold=0,
        )
        n_batches, batch_size = 30, 20
        batches = [
            [b"acc-%03d-%03d" % (i, j) for j in range(batch_size)]
            for i in range(n_batches)
        ]
        acked: list = []  # (batch_index, rid)
        errors: list = []
        killed = threading.Event()

        def writer():
            for i, keys in enumerate(batches):
                if i == 8:
                    killed.set()  # signal the main thread to SIGKILL
                try:
                    client.insert_batch("cnt", keys)
                    acked.append((i, client.last_rid))
                    continue
                except Exception as e:  # noqa: BLE001
                    errors.append((i, repr(e)))
                # the logical call exhausted its budget mid-failover:
                # keep re-driving with the SAME rid (a fresh rid could
                # double-apply a landed-but-unacked batch; the fixed
                # one answers from the dedup cache instead)
                rid = client.last_rid
                while True:
                    try:
                        client.refresh_topology()
                        client._call_once(
                            "InsertBatch",
                            {"name": "cnt", "keys": keys, "rid": rid},
                        )
                        acked.append((i, rid))
                        break
                    except Exception as e:  # noqa: BLE001
                        errors.append((i, repr(e)))
                        if len(errors) > 300:
                            raise
                        time.sleep(0.2)

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        assert killed.wait(60), "writer never reached the kill point"
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        t.join(timeout=180)
        assert not t.is_alive(), (
            f"writer wedged; acked={len(acked)} errors={errors[-3:]}"
        )
        assert len(acked) == n_batches, (
            f"client failed to complete all batches: {len(acked)}; "
            f"errors={errors[-3:]}"
        )

        # post-mortem (ISSUE 16): the SIGKILLed primary ran no handler,
        # but its mmap'd black box survives — it must decode into the
        # node's lifecycle and the pre-kill batches' spilled spans
        from tpubloom.obs import blackbox as bb

        node = bb.read_node(str(plog))
        assert node is not None, "SIGKILL must leave a readable black box"
        assert node["meta"].get("role") == "primary"
        assert "boot" in [e["kind"] for e in node["events"]]
        dead_rids = {s.get("rid") for s in node["spans"]}
        pre_kill = [rid for i, rid in acked if i < 8]
        assert pre_kill and set(pre_kill) <= dead_rids, (
            "pre-kill acked rids must have spilled spans in the dead "
            "primary's ring"
        )
        assert bb.merge_timeline([node], rid=pre_kill[-1])

        # the failover happened and the client followed it
        topo = fetch_topology([s.address for s in sents])
        assert topo is not None and topo["primary"] != f"127.0.0.1:{port}"
        new_primary = topo["primary"]
        assert client.address == new_primary

        # re-drive EVERY acked batch with its ORIGINAL rid against the
        # new primary: a batch that replicated before the kill answers
        # from the rid-dedup cache (no double), a batch whose ack raced
        # the kill applies now (no loss) — this is exactly the PR-2
        # dedup contract the ISSUE pins.
        redrive = BloomClient(new_primary)
        for i, rid in acked:
            redrive._call_once(
                "InsertBatch",
                {"name": "cnt", "keys": batches[i], "rid": rid},
            )

        # zero lost: every acknowledged key is present
        all_keys = [k for b in batches for k in b]
        assert redrive.include_batch("cnt", all_keys).all(), (
            "acknowledged writes lost across the failover"
        )
        # zero doubled: counting counts are exactly 1 -> one delete
        # round empties every key
        for i, _ in acked:
            redrive.delete_batch("cnt", batches[i])
        assert not redrive.include_batch("cnt", all_keys).any(), (
            "acknowledged writes double-applied across the failover"
        )
        redrive.close()

        # restart the old primary: stale epoch -> fenced to replica
        proc2 = subprocess.Popen(
            child_args,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        try:
            fence_probe = BloomClient(f"127.0.0.1:{port}")
            fence_probe.wait_ready(timeout=120)
            _wait(
                lambda: fence_probe.health()["role"] == "replica",
                timeout=30,
                msg="stale-primary fencing",
            )
            h = fence_probe.health()
            assert h["epoch"] == topo["epoch"]
            fence_probe.close()
        finally:
            proc2.send_signal(signal.SIGTERM)
            try:
                proc2.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc2.kill()
        client.close()
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


def test_ha_smoke():
    """benchmarks/ha_smoke.py runs in tier-1 so the failover surface
    cannot silently rot (and CI runs it standalone)."""
    import importlib
    import sys

    bench_dir = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, os.path.abspath(bench_dir))
    try:
        ha_smoke = importlib.import_module("ha_smoke")
        result = ha_smoke.run_smoke()
    finally:
        sys.path.pop(0)
    assert result["failovers"] >= 1
    assert result["lost_acked"] == 0
    assert result["double_applied"] == 0
    assert result["failover_seconds"] < 30


# -- review-hardening regressions --------------------------------------------


def test_demotion_never_drops_acked_writes_from_the_log(tmp_path):
    """Review finding: an in-flight write that passed the READONLY check
    before a demotion fence must still land in the op log (become_replica
    drains writers before the applier takes the log over) — every write
    the client saw acked is a record."""
    from tpubloom.ha.promotion import become_replica

    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}", max_retries=0)
    pc.wait_ready()
    pc.create_filter("d", capacity=10_000, error_rate=0.01)
    acked = []
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            key = b"w%014d" % i
            try:
                pc.insert_batch("d", [key])
            except Exception:  # noqa: BLE001 — READONLY fence, or the
                # client's auto-redirect chasing the (bogus) new primary
                return
            acked.append(key)
            i += 1

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    _wait(lambda: len(acked) > 5, msg="writer warm-up")
    try:
        # demote mid-stream (the target primary need not be reachable —
        # the drain + log handoff is what's under test)
        become_replica(psvc, "127.0.0.1:1")
        stop.set()
        t.join(timeout=30)
        assert not t.is_alive()
        logged = {
            k
            for r in poplog.read_from(0)
            if r["method"] == "InsertBatch"
            for k in r["req"]["keys"]
        }
        missing = [k for k in acked if k not in logged]
        assert not missing, (
            f"{len(missing)} acked write(s) vanished from the log across "
            f"the demotion fence, e.g. {missing[:3]}"
        )
    finally:
        stop.set()
        if psvc.replica_applier is not None:
            psvc.replica_applier.stop()
        pc.close()
        psrv.stop(grace=None)
        poplog.close()


def test_chained_replica_log_is_truncated(tmp_path, monkeypatch):
    """Review finding: the truncation sweep must run on the reappend
    path too, or a chained replica's log grows without bound."""
    from tpubloom.server import service as service_mod

    monkeypatch.setattr(service_mod, "TRUNCATE_EVERY_APPENDS", 4)
    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    pc.create_filter("t", capacity=10_000, error_rate=0.01)

    roplog = OpLog(str(tmp_path / "rlog"), segment_bytes=256)
    rsvc = BloomService(
        sink_factory=lambda config: ckpt.FileSink(str(tmp_path / "rck")),
        oplog=roplog,
        read_only=True,
    )
    rsrv, rport = build_server(rsvc, "127.0.0.1:0")
    rsrv.start()
    applier = ReplicaApplier(
        rsvc, f"127.0.0.1:{pport}", reconnect_base=0.05
    ).start()
    try:
        for i in range(16):
            pc.insert_batch("t", [b"a%05d" % i])
        assert applier.wait_for_seq(poplog.last_seq, 30), applier.status()
        assert roplog.stats()["segments"] > 1  # there IS something to GC
        rsvc.Checkpoint({"name": "t", "wait": True})  # covers everything
        for i in range(16):  # reappends drive the sweep past the ckpt
            pc.insert_batch("t", [b"b%05d" % i])
        assert applier.wait_for_seq(poplog.last_seq, 30), applier.status()
        assert roplog.first_seq > 1, (
            "chained replica log never truncated despite a covering "
            "checkpoint"
        )
    finally:
        applier.stop()
        pc.close()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()
        roplog.close()


def test_client_sentinels_unreachable_raises_no_topology():
    """Review finding: sentinel-resolved construction must not silently
    fall back to localhost when no sentinel answers."""
    with pytest.raises(BloomServiceError, match="NO_TOPOLOGY"):
        BloomClient(sentinels=["127.0.0.1:1"])
    # an explicit address stays a valid fallback
    c = BloomClient("127.0.0.1:2", sentinels=["127.0.0.1:1"])
    assert c.address == "127.0.0.1:2"
    c.close()


# -- ISSUE 13 (chaos-coverage closure): the promotion / vote / chained
# re-append fault points get their own armed drives --------------------------


def test_promote_fault_point_aborts_promotion_cleanly(tmp_path):
    """``ha.promote`` fires at the very top of replica→primary
    promotion: an armed firing must abort the flip BEFORE any state
    changed — the node stays a fenced read-only replica and a later
    (disarmed) promote succeeds from scratch."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    pc.create_filter("cnt", capacity=10_000, error_rate=0.01, counting=True)
    pc.insert_batch("cnt", [b"k%015d" % i for i in range(50)])

    rsvc, rsrv, rport, applier = _replica(
        tmp_path, pport, name="chainlog", chained=True
    )
    rc = BloomClient(f"127.0.0.1:{rport}")
    try:
        assert applier.wait_for_seq(poplog.last_seq, 30), applier.status()

        faults.arm("ha.promote", "always")
        with pytest.raises(BloomServiceError, match="INTERNAL"):
            rc.promote()
        # nothing flipped: still a fenced replica on epoch 0
        h = rc.health()
        assert h["role"] == "replica" and rsvc.read_only
        assert obs_counters.get("fault_ha_promote") >= 1
        # raw call (the stock client would auto-redirect to the primary):
        # the node itself still fences writes
        with pytest.raises(BloomServiceError, match="READONLY"):
            rc._call_once("InsertBatch", {"name": "cnt", "keys": [b"fenced"]})

        faults.disarm("ha.promote")  # the aborted promotion re-drives
        resp = rc.promote()
        assert resp["ok"] and not resp["already_primary"]
        assert rc.health()["role"] == "primary"
        rc.insert_batch("cnt", [b"post-promo-write"])
        assert rc.include("cnt", b"post-promo-write")
    finally:
        applier.stop()
        rc.close()
        pc.close()
        rsrv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()
        if rsvc.oplog is not None:
            rsvc.oplog.close()


def test_vote_fault_point_injects_into_grant_path():
    """``ha.vote`` armed: the grant path dies mid-election (the caller
    sees a dead peer, exactly what the quorum loop tolerates) and the
    vote is NOT spent — once disarmed the same epoch is still
    grantable, so an injected vote failure cannot silently burn the
    term the way a granted-then-lost frame would."""
    s = Sentinel("127.0.0.1:1", peers=[], quorum=2)
    s._sdown = True
    faults.arm("ha.vote", "once")
    with pytest.raises(faults.InjectedFault):
        s.handle_VoteDown({"epoch": 1, "primary": "127.0.0.1:1"})
    assert obs_counters.get("fault_ha_vote") >= 1
    # the fault fired BEFORE the vote registered: epoch 1 is still live
    assert s.handle_VoteDown({"epoch": 1, "primary": "127.0.0.1:1"})["granted"]
    # and the term discipline still holds afterwards
    assert not s.handle_VoteDown(
        {"epoch": 1, "primary": "127.0.0.1:1"}
    )["granted"]


def test_chained_reappend_fault_heals_exactly_once(tmp_path):
    """``repl.reappend`` armed on a chained replica: the write-ahead
    re-append dies, the applier reconnects, and the re-delivered record
    lands in the local log + filter exactly once (the chained log keeps
    the upstream seq space gap-free)."""
    psvc, psrv, pport, poplog = _primary(tmp_path)
    pc = BloomClient(f"127.0.0.1:{pport}")
    pc.wait_ready()
    keys = [b"r%015d" % i for i in range(150)]
    pc.create_filter("cnt", capacity=20_000, error_rate=0.01, counting=True)
    pc.insert_batch("cnt", keys)

    mid_svc, mid_srv, mid_port, mid_app = _replica(
        tmp_path, pport, name="midlog", chained=True
    )
    mc = BloomClient(f"127.0.0.1:{mid_port}")
    try:
        assert mid_app.wait_for_seq(poplog.last_seq, 30), mid_app.status()
        assert mid_svc.oplog.last_seq == poplog.last_seq

        before = obs_counters.get("fault_repl_reappend")
        faults.arm("repl.reappend", "once")
        live = [b"live-%07d" % i for i in range(40)]
        pc.insert_batch("cnt", live)
        assert mid_app.wait_for_seq(poplog.last_seq, 30), mid_app.status()
        assert obs_counters.get("fault_repl_reappend") == before + 1
        # the chained log re-converged on the upstream seq space
        assert mid_svc.oplog.last_seq == poplog.last_seq
        assert mc.include_batch("cnt", live).all()

        # exactly-once: ONE delete round empties every count
        pc.delete_batch("cnt", keys + live)
        assert mid_app.wait_for_seq(poplog.last_seq, 30)
        assert not mc.include_batch("cnt", keys + live).any(), (
            "re-delivered record double-applied through the chained log"
        )
    finally:
        mid_app.stop()
        mc.close()
        pc.close()
        mid_srv.stop(grace=None)
        psrv.stop(grace=None)
        poplog.close()
        if mid_svc.oplog is not None:
            mid_svc.oplog.close()
