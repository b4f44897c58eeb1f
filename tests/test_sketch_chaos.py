"""Sketch-plane chaos (ISSUE 19 acceptance).

* **fault points** — ``cuckoo.kick`` / ``cms.update`` fire BEFORE the
  kernel mutates anything, so a failed update applies nothing and the
  retry lands exactly once (one-copy delete / exact-weight proofs);
* **the acceptance** — a real subprocess server SIGKILLed after acking
  one delete of a doubly-inserted cuckoo key, weighted CMS increments,
  and top-k adds; restarted over the same op-log dir:

  - the acked ``CFDel`` replays exactly once — the key's SECOND copy
    is still present (a doubled replay would have eaten both);
  - CMS counts are neither lost nor doubled (weighted records replay
    with their exact weights);
  - the top-k heap rebuilds to the same estimates;
  - the killed process's black-box ring is readable post-mortem via
    ``python -m tpubloom.obs.blackbox``.
"""

import json
import os
import signal
import socket
import subprocess
import sys

import pytest

from tpubloom import faults
from tpubloom.server import protocol
from tpubloom.server.client import BloomClient
from tpubloom.server.ingest import CoalesceConfig
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


# -- fault-point chaos: fail-before-apply, retry exactly-once -----------------


def test_cuckoo_kick_fault_fails_flush_then_heals(coalesced_server):
    """``cuckoo.kick`` fires before the insert kernel runs: the
    coalesced flush errors, NOTHING lands, and the retry applies each
    key exactly once (one-copy delete proof: after one delete per key
    the filter is empty again)."""
    s = coalesced_server
    with s.client() as c:
        c.cf_reserve("chaos-cf", 1000)
        keys = [b"ck-%d" % j for j in range(32)]
        faults.arm("cuckoo.kick", "once")
        with pytest.raises(protocol.BloomServiceError) as ei:
            c.cf_add("chaos-cf", keys)
        assert ei.value.code == "INTERNAL"
        assert not c.cf_exists("chaos-cf", keys).any(), (
            "a failed kick batch must not have applied"
        )
        assert c.cf_add("chaos-cf", keys).all()  # heals
        assert c.cf_del("chaos-cf", keys).all()
        assert not c.cf_exists("chaos-cf", keys).any(), (
            "double-applied: one delete per key left residue"
        )


def test_cms_update_fault_fails_weighted_incr_then_heals(coalesced_server):
    """``cms.update`` fires before the scatter-add: the weighted
    increment errors with counts untouched, and the retry lands the
    exact weights once (7 stays 7, not 14)."""
    s = coalesced_server
    with s.client() as c:
        c.cms_init_by_dim("chaos-cms", 128, 4)
        faults.arm("cms.update", "once")
        with pytest.raises(protocol.BloomServiceError) as ei:
            c.cms_incrby("chaos-cms", [b"hot"], [7])
        assert ei.value.code == "INTERNAL"
        assert c.cms_query("chaos-cms", [b"hot"])[0] == 0
        counts = c.cms_incrby("chaos-cms", [b"hot"], [7])
        assert counts[0] == 7
        assert c.cms_query("chaos-cms", [b"hot"])[0] == 7


def test_cms_update_fault_fails_coalesced_unit_adds(coalesced_server):
    """Unit increments ride the coalescer as InsertBatch: an armed
    ``cms.update`` errors the whole parked flush pre-apply and the
    retry counts each key exactly once."""
    s = coalesced_server
    with s.client() as c:
        c.cms_init_by_dim("chaos-cms2", 128, 4)
        keys = [b"u-%d" % j for j in range(16)]
        faults.arm("cms.update", "once")
        with pytest.raises(protocol.BloomServiceError):
            c.cms_incrby("chaos-cms2", keys)
        assert not c.cms_query("chaos-cms2", keys).any()
        c.cms_incrby("chaos-cms2", keys)
        assert (c.cms_query("chaos-cms2", keys) == 1).all()


# -- the acceptance: SIGKILL + restart replay, per kind -----------------------

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


def test_sigkill_replays_each_sketch_kind_exactly_once(tmp_path):
    """THE ISSUE-19 acceptance: SIGKILL a real subprocess server after
    acked sketch writes per kind; restart it over the same op-log dir;
    every replay-unsafe record applies EXACTLY once:

    * cuckoo — ``dup`` was inserted twice and deleted once pre-kill.
      After restart exactly one copy remains: a lost delete would show
      two (second delete would still leave one), a doubled delete zero.
    * cms — the acked weighted counts read back bit-identical (lost
      replay reads low, doubled reads 2x).
    * topk — the heap rebuilds to the same estimates.
    """
    plog = tmp_path / "primary-log"
    port = _free_port()
    args = [port, tmp_path / "ckpt", "--repl-log-dir", plog,
            "--coalesce-max-keys", "4096", "--coalesce-max-wait-us", "2000",
            "--trace-sample", "0.0"]
    proc = _spawn(tmp_path, "server-a.py", args)
    restarted = None

    def _dial():
        return BloomClient(
            f"127.0.0.1:{port}", timeout=30.0,
            max_retries=120, backoff_base=0.25, backoff_max=1.0,
        )

    client = _dial()
    try:
        client.wait_ready(timeout=120)
        client.cf_reserve("cf", 5000)
        client.cms_init_by_dim("cms", 128, 4)
        client.topk_reserve("tk", 3, width=128, depth=4)

        singles = [b"s-%02d" % j for j in range(16)]
        assert client.cf_add("cf", [b"dup", b"dup"] + singles).all()
        assert client.cf_del("cf", [b"dup"]).all()  # acked: one copy gone
        counts = client.cms_incrby("cms", [b"hot", b"warm"], [7, 3])
        assert counts == [7, 3]
        client.topk_add("tk", [b"hot"] * 5 + [b"cold"])
        hitters = dict(client.topk_list("tk"))
        assert hitters[b"hot"] == 5

        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        restarted = _spawn(tmp_path, "server-b.py", args)
        # unary plane: re-dial on a fresh channel (the killed server's
        # channel can sit in gRPC reconnect backoff past the restart;
        # session-level channel survival is test_streams' acceptance)
        client.close()
        client = _dial()
        client.wait_ready(timeout=120)

        # cuckoo: exactly one copy of "dup" survived the replay
        assert client.cf_exists("cf", [b"dup"])[0], (
            "the acked delete replayed twice: both copies are gone"
        )
        assert client.cf_del("cf", [b"dup"]).all()
        assert not client.cf_exists("cf", [b"dup"])[0], (
            "the acked delete was lost: two copies survived the kill"
        )
        assert client.cf_exists("cf", singles).all()

        # cms: weighted counts neither lost nor doubled
        after = client.cms_query("cms", [b"hot", b"warm"])
        assert after.tolist() == counts

        # topk: heap rebuilt from the replayed adds, same estimates
        assert dict(client.topk_list("tk")) == hitters
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

    # post-mortem: the KILLED server's mmap'd black-box ring survived
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
