#!/usr/bin/env python
"""Chip smoke: the served filter path, end to end, on a TPU.

``python chip_smoke.py`` boots ``python -m tpubloom.server`` — the only
process that touches the chip; this parent stays on the gRPC client and
the NumPy oracle and never starts a JAX backend — and then:

1. checks the server's device (``Health``: backend ``tpu``, one device)
   before any load;
2. creates the BASELINE north-star filter (m=2^32, k=7, key_len=16,
   block_bits=512: 512 MiB in HBM) and inserts 8 batches of 1,048,576
   random 16-byte keys made from ``--seed``, every other batch through
   the fused test-and-insert (``return_presence``);
3. queries 1M inserted and 1M fresh keys; every verdict and presence bit
   must equal ``CPUBlockedBloomFilter`` fed the same keys; the kernel
   paths the server logged for the 1M-key batches must be ``sweep`` and
   ``tpubloom_geometry_probe_demotions_total`` must be 0;
4. checkpoints, stops the server with SIGTERM, boots a second one on the
   same checkpoint directory, restores the filter and re-queries: the
   verdicts must not change.

``--chips 4`` instead runs the filter spread over a 4-chip mesh
(shards=32, m=2^35: 8 shard rows and 1 GiB per chip) against a routed
CPU oracle, and no other phase.

Earlier lines report phase timings, the device, kernel paths and each
boot's time to its first answered query. The last line is one JSON
object, printed only when every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.request

import numpy as np

from tpubloom.config import FilterConfig
from tpubloom.cpu_ref import CPUBlockedBloomFilter, murmur3_32_np
from tpubloom.ops.hashing import SEED_XOR_ROUTE
from tpubloom.params import blocked_fpr
from tpubloom.server.client import BloomClient
from tpubloom.utils.packing import pack_keys

REPO = os.path.dirname(os.path.abspath(__file__))
#: BASELINE north star: 512 MiB resident in HBM
FLAGSHIP = {"m": 1 << 32, "k": 7, "key_len": 16, "block_bits": 512}
#: the per-chip share of BASELINE config 5 on a 4-chip host
SHARDED = {"m": 1 << 35, "k": 7, "key_len": 16, "block_bits": 512, "shards": 32}
BATCH = 1 << 20
NAME = "smoke"
RPC_TIMEOUT_S = 900.0


class SmokeError(Exception):
    """A phase failed: the script exits non-zero and prints no result."""


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """One ``python -m tpubloom.server`` child, its log in ``log_path``."""

    def __init__(self, ckpt_dir: str, log_path: str):
        self.port, self.metrics_port = _free_port(), _free_port()
        self.address = f"127.0.0.1:{self.port}"
        self.log_path = log_path
        self.t0 = time.perf_counter()
        with open(log_path, "ab") as log:
            self.proc = subprocess.Popen(
                [
                    sys.executable, "-m", "tpubloom.server", str(self.port),
                    ckpt_dir, "--metrics-port", str(self.metrics_port),
                ],
                cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
            )

    def client(self) -> BloomClient:
        return BloomClient(self.address, timeout=RPC_TIMEOUT_S)

    def wait_ready(self, timeout: float = 600.0) -> dict:
        """Health once the server answers; a fresh client per attempt
        (a reused one's circuit breaker opens during the boot window)."""
        deadline = time.monotonic() + timeout
        while True:
            if self.proc.poll() is not None:
                raise SmokeError(
                    f"server exited with {self.proc.returncode} during boot"
                )
            try:
                with BloomClient(self.address, timeout=60.0, max_retries=0) as c:
                    return c.health()
            except Exception as e:  # noqa: BLE001 — still booting
                if time.monotonic() > deadline:
                    raise SmokeError(f"server not ready in {timeout}s: {e}")
            time.sleep(0.5)

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def device(self) -> dict:
        """The device as the server's JAX reports it (logged at boot)."""
        m = re.search(
            r"jax devices: platform=(\S+) kind=(.+?) count=(\d+)",
            self.log_text(),
        )
        if m is None:
            raise SmokeError("server logged no device line")
        return {"platform": m[1], "kind": m[2], "count": int(m[3])}

    def metric(self, name: str) -> float:
        """One unlabelled series from /metrics (0 when not yet touched)."""
        url = f"http://127.0.0.1:{self.metrics_port}/metrics"
        with urllib.request.urlopen(url, timeout=30) as r:
            text = r.read().decode()
        m = re.search(rf"^{re.escape(name)} (\S+)$", text, re.M)
        return float(m[1]) if m else 0.0

    def stop(self, timeout: float = 600.0) -> None:
        """SIGTERM (drain + final checkpoint) and wait for the exit, so
        that one process holds the chip at a time."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                raise SmokeError(f"server did not drain in {timeout}s")
        if self.proc.returncode != 0:
            raise SmokeError(f"server exited with {self.proc.returncode}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()


def check_device(server: Server, platform: str, count: int) -> dict:
    """Health must report ``platform`` with exactly ``count`` devices;
    returns the device as the server's JAX reports it."""
    h = server.wait_ready()
    backend, devices = h.get("backend"), h.get("devices") or []
    if backend != platform or len(devices) != count:
        raise SmokeError(
            f"device check: backend {backend!r} with {len(devices)} "
            f"device(s); need {platform!r} x {count}"
        )
    dev = server.device()
    say(f"device: {dev['platform']} {dev['kind']} x{dev['count']}")
    return dev


def make_keys(rng: np.random.Generator, n: int, key_len: int) -> list:
    raw = rng.integers(0, 256, (n, key_len), dtype=np.uint8).tobytes()
    return [raw[i * key_len:(i + 1) * key_len] for i in range(n)]


def kernel_paths(log_text: str, batch: int) -> dict:
    """Kernel path per blocked op for ``batch``-key launches, as the
    server logged them when jit traced that shape."""
    return {
        op: path
        for op, path, b in re.findall(
            r"blocked (insert|test_insert|query) path=(\w+) batch=(\d+)",
            log_text,
        )
        if int(b) == batch
    }


def compare(what: str, got: np.ndarray, want: np.ndarray) -> None:
    got, want = np.asarray(got, bool), np.asarray(want, bool)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = int(np.sum(got != want)) if got.shape == want.shape else -1
        raise SmokeError(
            f"{what}: {bad} of {want.size} verdicts differ from the CPU oracle"
        )


def _timed(t: dict, key: str, t0: float) -> None:
    t[key] = time.perf_counter() - t0
    say(f"{key}={t[key]:.3f}")


def run_single(
    cfg: dict, *, batch: int, n_batches: int, seed: int, platform: str,
    count: int, want_path: str | None, workdir: str,
) -> dict:
    """Load, query, checkpoint, restart, re-query — oracle-checked."""
    ckpt = os.path.join(workdir, "ckpt")
    t: dict = {}
    servers: list = []
    try:
        srv = Server(ckpt, os.path.join(workdir, "server1.log"))
        servers.append(srv)
        dev = check_device(srv, platform, count)
        _timed(t, "boot1_ready_s", srv.t0)
        c = srv.client()
        t0 = time.perf_counter()
        config = FilterConfig.from_dict(
            c.create_filter(NAME, config=cfg)["config"]
        )
        _timed(t, "boot1_create_s", t0)
        rng = np.random.default_rng(seed)
        fresh = make_keys(rng, batch, config.key_len)
        t0 = time.perf_counter()
        compare("empty-filter query", c.include_batch(NAME, fresh), np.zeros(batch))
        _timed(t, "boot1_first_query_s", t0)
        _timed(t, "boot1_first_answer_s", srv.t0)

        t0 = time.perf_counter()
        batches = [make_keys(rng, batch, config.key_len) for _ in range(n_batches)]
        # the last batch replays half of the first: its presence bits
        # must come back set
        half = batch // 2
        batches[-1][:half] = batches[0][:half]
        flat = [k for b in batches for k in b]
        present = [flat[i] for i in rng.choice(len(flat), batch, replace=False)]
        oracle = CPUBlockedBloomFilter(config)
        _timed(t, "keygen_s", t0)

        t0 = time.perf_counter()
        for i, keys in enumerate(batches):
            if i % 2:
                want = oracle.include_batch(keys)
                got = c.insert_batch(NAME, keys, return_presence=True)
                compare(f"presence of batch {i}", got, want)
            else:
                c.insert_batch(NAME, keys)
            oracle.insert_batch(keys)
        if not oracle.include_batch(batches[-1][:half]).all():
            raise SmokeError("oracle lost replayed keys")
        _timed(t, "load_s", t0)

        t0 = time.perf_counter()
        hits_in = c.include_batch(NAME, present)
        hits_out = c.include_batch(NAME, fresh)
        _timed(t, "query_s", t0)
        if not hits_in.all():
            raise SmokeError("an inserted key queried absent")
        compare("inserted-key query", hits_in, oracle.include_batch(present))
        compare("fresh-key query", hits_out, oracle.include_batch(fresh))
        n_distinct = n_batches * batch - half
        say(
            f"fpr observed={float(hits_out.mean())!r} theoretical="
            f"{blocked_fpr(n_distinct, m=config.m, k=config.k, block_bits=config.block_bits, block_hash=config.block_hash)!r}"
        )

        paths = kernel_paths(srv.log_text(), batch)
        say(f"kernel paths at batch {batch}: {json.dumps(paths, sort_keys=True)}")
        if set(paths) != {"insert", "test_insert", "query"}:
            raise SmokeError(f"server logged no path for {paths}")
        if want_path and any(p != want_path for p in paths.values()):
            raise SmokeError(f"kernel paths {paths}, need {want_path!r}")
        demotions = srv.metric("tpubloom_geometry_probe_demotions_total")
        say(f"tpubloom_geometry_probe_demotions_total={demotions!r}")
        if demotions > 0:
            raise SmokeError("a geometry probe demoted a kernel")

        t0 = time.perf_counter()
        c.checkpoint(NAME)
        _timed(t, "checkpoint_s", t0)
        c.close()
        t0 = time.perf_counter()
        srv.stop()
        _timed(t, "drain_s", t0)

        srv = Server(ckpt, os.path.join(workdir, "server2.log"))
        servers.append(srv)
        check_device(srv, platform, count)
        _timed(t, "boot2_ready_s", srv.t0)
        c = srv.client()
        t0 = time.perf_counter()
        c.create_filter(NAME, config=cfg)
        _timed(t, "boot2_restore_s", t0)
        t0 = time.perf_counter()
        again_in = c.include_batch(NAME, present)
        _timed(t, "boot2_first_query_s", t0)
        _timed(t, "boot2_first_answer_s", srv.t0)
        again_out = c.include_batch(NAME, fresh)
        n_restored = c.stats(NAME).get("n_inserted")
        if n_restored != n_batches * batch:
            raise SmokeError(f"restored n_inserted={n_restored}")
        compare("inserted-key query after restart", again_in, hits_in)
        compare("fresh-key query after restart", again_out, hits_out)
        demotions = srv.metric("tpubloom_geometry_probe_demotions_total")
        if demotions > 0:
            raise SmokeError("a geometry probe demoted a kernel after restart")
        c.close()
        srv.stop()
        return {"device": dev, "timings": t, "paths": paths}
    finally:
        for s in servers:
            s.kill()


class RoutedOracle:
    """CPU reference of the sharded filter: the routing hash, then one
    ``CPUBlockedBloomFilter`` per shard (as tests/test_sharded.py's
    ``ShardedCPURef`` does), on the native hash path."""

    def __init__(self, config: FilterConfig):
        self.config = config
        local = config.replace(m=config.m_per_shard, shards=1)
        self.filters = [CPUBlockedBloomFilter(local) for _ in range(config.shards)]

    def _route(self, keys: list) -> np.ndarray:
        ku8, lens = pack_keys(keys, self.config.key_len)
        seed = self.config.seed ^ SEED_XOR_ROUTE
        return murmur3_32_np(ku8, lens, seed) % np.uint32(self.config.shards)

    def insert_batch(self, keys: list) -> None:
        routes = self._route(keys)
        for s in np.unique(routes):
            idx = np.flatnonzero(routes == s)
            self.filters[s].insert_batch([keys[i] for i in idx])

    def include_batch(self, keys: list) -> np.ndarray:
        routes = self._route(keys)
        out = np.zeros(len(keys), bool)
        for s in np.unique(routes):
            idx = np.flatnonzero(routes == s)
            out[idx] = self.filters[s].include_batch([keys[i] for i in idx])
        return out


def shard_placement(log_text: str) -> dict:
    """Device -> shard rows, as the server logged the filter's placement."""
    out: dict = {}
    for lo, hi, dev in re.findall(r"shard rows (\d+)-(\d+) on (.+)", log_text):
        out.setdefault(dev.strip(), []).extend(range(int(lo), int(hi) + 1))
    return out


def run_sharded(
    cfg: dict, *, batch: int, n_batches: int, seed: int, platform: str,
    count: int, workdir: str,
) -> dict:
    """The filter spread over ``count`` devices, oracle-checked."""
    t: dict = {}
    srv = Server(os.path.join(workdir, "ckpt"), os.path.join(workdir, "server.log"))
    try:
        dev = check_device(srv, platform, count)
        c = srv.client()
        config = FilterConfig.from_dict(
            c.create_filter(NAME, config=cfg)["config"]
        )
        placement = shard_placement(srv.log_text())
        for d, rows in sorted(placement.items()):
            say(f"shard rows {rows[0]}-{rows[-1]} on {d}")
        rows = sorted(r for rs in placement.values() for r in rs)
        if len(placement) != count or rows != list(range(config.shards)):
            raise SmokeError(f"shard rows not spread over {count} devices")
        rng = np.random.default_rng(seed)
        t0 = time.perf_counter()
        batches = [make_keys(rng, batch, config.key_len) for _ in range(n_batches)]
        fresh = make_keys(rng, batch, config.key_len)
        flat = [k for b in batches for k in b]
        present = [flat[i] for i in rng.choice(len(flat), batch, replace=False)]
        oracle = RoutedOracle(config)
        _timed(t, "keygen_s", t0)
        t0 = time.perf_counter()
        for keys in batches:
            c.insert_batch(NAME, keys)
            oracle.insert_batch(keys)
        _timed(t, "load_s", t0)
        t0 = time.perf_counter()
        hits_in = c.include_batch(NAME, present)
        hits_out = c.include_batch(NAME, fresh)
        _timed(t, "query_s", t0)
        if not hits_in.all():
            raise SmokeError("an inserted key queried absent")
        compare("inserted-key query", hits_in, oracle.include_batch(present))
        compare("fresh-key query", hits_out, oracle.include_batch(fresh))
        say(f"fpr observed={float(hits_out.mean())!r}")
        c.close()
        srv.stop()
        return {"device": dev, "timings": t, "placement": placement}
    finally:
        srv.kill()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the filter sharded over a 4-chip mesh",
    )
    ap.add_argument("--seed", type=int, default=0, help="key generator seed")
    args = ap.parse_args(argv)
    from tpubloom.utils import compile_cache

    say(f"compile cache: {compile_cache.configure()}")
    workdir = tempfile.mkdtemp(prefix="chip_smoke-")
    try:
        if args.chips == 4:
            report = run_sharded(
                SHARDED, batch=BATCH, n_batches=4, seed=args.seed,
                platform="tpu", count=4, workdir=workdir,
            )
        else:
            report = run_single(
                FLAGSHIP, batch=BATCH, n_batches=8, seed=args.seed,
                platform="tpu", count=1, want_path="sweep", workdir=workdir,
            )
    except Exception as e:  # noqa: BLE001 — any failed phase: report, exit 1
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        if not isinstance(e, SmokeError):
            traceback.print_exc()
        for log in sorted(os.listdir(workdir)):
            if log.endswith(".log"):
                with open(os.path.join(workdir, log), errors="replace") as f:
                    tail = f.read()[-6000:]
                print(f"--- {log} (tail)\n{tail}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": report["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
