#!/usr/bin/env python
"""Saturating multi-connection ingest load generator (ISSUE 10).

Makes aggregate END-TO-END keys/sec a first-class tracked metric instead
of an H2D-bound afterthought: a real subprocess server (so the measurement
includes gRPC, decode, scheduling — everything a production client
pays), one warm connection measured alone, then N concurrent
connections hammering the same filter through the ingestion coalescer
with the zero-copy ``fixed`` wire encoding.

What the numbers mean:

* ``single_conn_keys_per_sec`` — one connection's ping-pong rate: every
  request pays the full per-request cost (rtt + decode + lock + jit
  dispatch + the coalesce window) serially;
* ``aggregate_keys_per_sec`` — N connections, coalesced: concurrent
  requests park and flush as ONE device launch, so the per-request
  fixed costs amortize across the flush;
* ``scaling_vs_single`` — aggregate / single. THE acceptance gate
  (``>= 2.0``, re-measured once with a doubled window before failing,
  like cluster_smoke's): on one shared filter the lock-serialized
  per-request path barely scales with connections (measured ~1.3x on
  this CPU image — every request runs its own kernel under the op
  lock), so clearing 2x is the coalescer's amortization, not thread
  parallelism;
* ``requests_per_flush`` — how many RPCs each device launch served
  (from the server's ingest counters; asserted > 1.5 so the gate can't
  pass without actual coalescing);
* ``scaling_vs_linear`` — aggregate / (N x single), informational. On
  a REAL TPU the host-side per-request cost dominates and this is the
  number to chase; on the CPU CI image the "device" is the same cores
  the handlers run on, so per-key kernel cost (~3us/key measured)
  bounds any single-dispatcher aggregate.

A **streaming phase** (ISSUE 18) pits the persistent bidi ingest plane
against unary on the same server: N ``insert_stream`` sessions pumping
``BATCH``-key frames under credit flow control vs N unary connections
sending the same frames. The gate is ``streaming_vs_unary >= 1.0``
(re-measured once with a doubled window, like the coalesce gate) — a
long-lived stream pays no per-request channel bookkeeping, so falling
BELOW unary means the ack pump or credit path regressed. Anti-gaming:
every frame the rate counts must show up in the server's
``stream_frames_total`` / ``stream_acks_total`` deltas — the clock only
stops after ``drain()``, so unacked frames can't inflate the number.

A second phase (skippable via ``quorum=False``) runs a primary+replica
pair with ``--min-replicas-to-write 1``: the commit barrier must run
once per FLUSH, not once per write — the run asserts barrier
observations (``wait_barrier`` histogram count) land well below the
quorum-write count, the "N quorum writes, one WAIT" amortization.

Run directly (prints one JSON line) or via tier-1
(``tests/test_ingest.py::test_ingest_load_smoke``).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np

#: concurrent connections in the aggregate phase.
CONNECTIONS = 8
#: keys per request — small on purpose: the gap this closes is
#: per-REQUEST overhead, and tiny requests are what real multi-tenant
#: front-ends send.
BATCH = 64
#: acceptance gate: N coalesced connections must beat ONE connection's
#: rate by this factor (the lock-serialized path measures ~1.3x here).
GATE = 2.0
#: streaming gate (ISSUE 18): bidi stream frames/sec vs unary frames/sec
#: on the same server — the persistent plane must at least match unary.
STREAM_GATE = 1.0

_CHILD = """\
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


def _spawn(
    tmpdir: str, idx: int, extra_args: list, *,
    child_src: str = None, env_extra: dict = None, env_drop: tuple = (),
) -> tuple:
    """Boot one subprocess server. ``child_src`` overrides the CPU-pinned
    default script; ``env_extra``/``env_drop`` adjust the child env
    (multichip_load uses them for the forced device mesh, the native-
    backend mode, and to strip debug instrumentation its perf gates
    must not measure)."""
    port = _free_port()
    script = os.path.join(tmpdir, f"child-{idx}.py")
    with open(script, "w") as f:
        f.write(child_src or _CHILD)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    for k in env_drop:
        env.pop(k, None)
    if env_extra:
        env.update(env_extra)
    proc = subprocess.Popen(
        [sys.executable, script, str(port), *extra_args],
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT, env=env,
    )
    return proc, f"127.0.0.1:{port}"


def _hammer(
    addr: str, name: str, threads: int, duration_s: float,
    *, tolerate: tuple = (),
) -> float:
    """Aggregate keys/sec of `threads` writer CONNECTIONS (one client =
    one channel each) inserting disjoint u64 batches. ``tolerate`` names
    error codes to ride through (the quorum phase tolerates
    NOT_ENOUGH_REPLICAS: the write APPLIED — Redis WAIT semantics — and
    a slow CI box stalling one barrier must not kill the run)."""
    from tpubloom.server import protocol
    from tpubloom.server.client import BloomClient

    clients = [BloomClient(addr) for _ in range(threads)]
    for c in clients:  # negotiate + warm the channel outside the window
        c.insert_batch(name, np.arange(BATCH, dtype=np.uint64))
    stop = time.monotonic() + duration_s
    counts = [0] * threads

    def worker(t):
        c = clients[t]
        base = np.arange(BATCH, dtype=np.uint64) + (t + 1) * (1 << 40)
        i = 0
        while time.monotonic() < stop:
            try:
                c.insert_batch(name, base + i * BATCH)
            except protocol.BloomServiceError as e:
                if e.code not in tolerate:
                    raise
            counts[t] += BATCH
            i += 1

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    rate = sum(counts) / (time.perf_counter() - t0)
    for c in clients:
        c.close()
    return rate


def _stream_hammer(
    addr: str, name: str, threads: int, duration_s: float
) -> tuple:
    """(frames/sec, frames sent) over `threads` persistent bidi
    InsertStream sessions, each pumping BATCH-key frames as fast as the
    server's credit window admits them. The clock stops only after every
    session DRAINED — a frame counts when its ack arrived, the same
    contract the unary hammer's response-wait gives."""
    from tpubloom.server.client import BloomClient

    clients = [BloomClient(addr) for _ in range(threads)]
    for c in clients:  # negotiate + warm the channel outside the window
        c.insert_batch(name, np.arange(BATCH, dtype=np.uint64))
    stop = time.monotonic() + duration_s
    counts = [0] * threads

    def worker(t):
        c = clients[t]
        base = np.arange(BATCH, dtype=np.uint64) + (t + 1) * (1 << 44)
        sent = 0
        with c.insert_stream(name) as s:
            while time.monotonic() < stop:
                s.send(base + sent * BATCH)
                sent += 1
            s.drain(timeout=120)
        counts[t] = sent

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    elapsed = time.perf_counter() - t0
    for c in clients:
        c.close()
    return sum(counts) / elapsed, sum(counts)


def _stream_counters(client) -> tuple:
    counters = client.stats()["counters"]
    return (
        counters.get("stream_frames_total", 0),
        counters.get("stream_acks_total", 0),
    )


def _measure_streaming(addr: str, name: str, duration_s: float,
                       stats_client) -> dict:
    unary = _hammer(addr, name, CONNECTIONS, duration_s)
    f0, a0 = _stream_counters(stats_client)
    stream_rate, frames_sent = _stream_hammer(
        addr, name, CONNECTIONS, duration_s
    )
    f1, a1 = _stream_counters(stats_client)
    unary_frames = unary / BATCH
    return {
        "unary_frames_per_sec": round(unary_frames),
        "stream_frames_per_sec": round(stream_rate),
        "streaming_vs_unary": round(stream_rate / unary_frames, 3),
        "stream_frames_sent": frames_sent,
        "stream_frames_recv": f1 - f0,
        "stream_acks_recv": a1 - a0,
    }


def _warm_buckets(client, name: str, up_to: int = None) -> None:
    """Compile every jit bucket a coalesced flush can produce (merged
    sizes pad to powers of two in [BATCH, up_to]) — without
    this the aggregate window eats one ~0.4s XLA compile per new shape
    and the measurement is compile time, not ingest time (the same
    lesson cluster_smoke's warm-up comment pins). Unary ping-pong with
    CONNECTIONS in flight can merge at most CONNECTIONS*BATCH keys (the
    default); the streaming phase pipelines a 32-frame window per
    session, so its flushes grow to the coalescer's max-keys cap and it
    warms that far."""
    from tpubloom.server import protocol

    size = BATCH
    while size <= (up_to or CONNECTIONS * BATCH):
        try:
            client.insert_batch(
                name, np.arange(size, dtype=np.uint64) + (1 << 50) + size
            )
        except protocol.BloomServiceError as e:
            if e.code != "NOT_ENOUGH_REPLICAS":  # applied; compile landed
                raise
        size *= 2


def _ingest_counters(client) -> tuple:
    counters = client.stats()["counters"]
    return (
        counters.get("ingest_flushes", 0),
        counters.get("ingest_requests_coalesced", 0),
    )


def _measure(addr: str, name: str, duration_s: float, stats_client) -> dict:
    single = _hammer(addr, name, 1, duration_s)
    f0, r0 = _ingest_counters(stats_client)
    aggregate = _hammer(addr, name, CONNECTIONS, duration_s)
    f1, r1 = _ingest_counters(stats_client)
    return {
        "single_conn_keys_per_sec": round(single),
        "aggregate_keys_per_sec": round(aggregate),
        "scaling_vs_single": round(aggregate / single, 3),
        "scaling_vs_linear": round(aggregate / (CONNECTIONS * single), 3),
        "ingest_flushes": f1 - f0,
        # requests/flush over the AGGREGATE window only (the single-
        # connection phase is 1/flush by construction)
        "requests_per_flush": round((r1 - r0) / max(f1 - f0, 1), 2),
    }


def run_load(
    duration_s: float = 2.0,
    *,
    quorum: bool = True,
    coalesce_args: tuple = ("--coalesce-max-keys", "16384",
                            "--coalesce-max-wait-us", "2000"),
) -> dict:
    import tempfile

    from tpubloom.server import protocol
    from tpubloom.server.client import BloomClient

    tmpdir = tempfile.mkdtemp(prefix="tpubloom-ingest-load-")
    procs: list = []
    out: dict = {
        "connections": CONNECTIONS, "batch": BATCH,
        "duration_s": duration_s,
    }
    try:
        proc, addr = _spawn(tmpdir, 0, list(coalesce_args))
        procs.append(proc)
        boot = BloomClient(addr)
        boot.wait_ready(timeout=180.0)
        boot.create_filter("ingest", capacity=1_000_000, error_rate=0.01)
        _warm_buckets(boot, "ingest")

        out.update(_measure(addr, "ingest", duration_s, boot))
        if out["scaling_vs_single"] < GATE or out["requests_per_flush"] <= 1.5:
            # one re-measure with a doubled window before failing: on a
            # small shared CI runner a scheduler hiccup inside a 2s
            # window can flip the comparison with no code defect
            out["remeasured"] = True
            out.update(_measure(addr, "ingest", duration_s * 2, boot))
        # streaming plane (ISSUE 18): same server, same frames — the
        # persistent stream must at least match unary frame throughput.
        # Pipelined windows park enough to hit the coalescer's max-keys
        # cap, so the jit buckets up to it must be warm first.
        max_keys = BATCH
        for flag, value in zip(coalesce_args, coalesce_args[1:]):
            if flag == "--coalesce-max-keys":
                max_keys = int(value)
        _warm_buckets(boot, "ingest", up_to=max_keys)
        out.update(_measure_streaming(addr, "ingest", duration_s, boot))
        if out["streaming_vs_unary"] < STREAM_GATE:
            out["stream_remeasured"] = True
            out.update(
                _measure_streaming(addr, "ingest", duration_s * 2, boot)
            )
        boot.close()
        assert out["streaming_vs_unary"] >= STREAM_GATE, (
            f"bidi streaming moved {out['stream_frames_per_sec']} "
            f"frames/s vs {out['unary_frames_per_sec']} unary — a "
            f"persistent stream below unary means the ack pump or "
            f"credit path regressed (gate {STREAM_GATE}x)"
        )
        # anti-gaming: every frame the rate counted must have been
        # RECEIVED and ACKED by the server during the window — a rate
        # computed off unsent/unacked frames cannot clear this
        assert out["stream_frames_recv"] >= out["stream_frames_sent"], (
            f"server received {out['stream_frames_recv']} stream frames "
            f"but the rate counted {out['stream_frames_sent']}"
        )
        assert out["stream_acks_recv"] >= out["stream_frames_sent"], (
            f"server acked {out['stream_acks_recv']} stream frames "
            f"but the rate counted {out['stream_frames_sent']}"
        )
        assert out["scaling_vs_single"] >= GATE, (
            f"coalesced aggregate ({out['aggregate_keys_per_sec']} keys/s "
            f"over {CONNECTIONS} connections) is only "
            f"{out['scaling_vs_single']}x the single-connection rate "
            f"({out['single_conn_keys_per_sec']}) — coalescing must "
            f"amortize per-request decode+launch (gate {GATE}x)"
        )
        assert out["requests_per_flush"] > 1.5, (
            f"only {out['requests_per_flush']} requests/flush — the "
            f"aggregate gate passed without actual coalescing"
        )

        if quorum:
            # barrier amortization: primary + one replica, every write
            # quorum-gated — the coalesced flush must pay ONE wait per
            # flush, not one per request
            pproc, paddr = _spawn(
                tmpdir, 1,
                [os.path.join(tmpdir, "ckpt-p"),
                 "--repl-log-dir", os.path.join(tmpdir, "log-p"),
                 "--min-replicas-to-write", "1",
                 # generous barrier budget: under the armed lock tracker
                 # (CI chaos shard) replica applies slow down and a 1s
                 # default budget flakes with no code defect
                 "--min-replicas-max-lag-ms", "5000",
                 *coalesce_args],
            )
            procs.append(pproc)
            pc = BloomClient(paddr)
            pc.wait_ready(timeout=180.0)
            rproc, raddr = _spawn(
                tmpdir, 2,
                [os.path.join(tmpdir, "ckpt-r"), "--replica-of", paddr],
            )
            procs.append(rproc)
            BloomClient(raddr).wait_ready(timeout=180.0)
            deadline = time.monotonic() + 60
            while True:  # wait for the replica to connect + ack
                if pc.health().get("replication", {}).get("replicas"):
                    break
                assert time.monotonic() < deadline, "replica never connected"
                time.sleep(0.2)
            try:
                pc.create_filter("q", capacity=1_000_000, error_rate=0.01)
            except protocol.BloomServiceError as e:
                # applied either way (WAIT semantics) — attach instead
                if e.code != "NOT_ENOUGH_REPLICAS":
                    raise
                pc.create_filter(
                    "q", capacity=1_000_000, error_rate=0.01, exist_ok=True
                )
            _warm_buckets(pc, "q")
            waits0 = pc.stats()["wait_barrier"].get("n", 0)
            r0 = pc.stats()["counters"].get("ingest_requests_coalesced", 0)
            q = _hammer(
                paddr, "q", CONNECTIONS, duration_s,
                tolerate=("NOT_ENOUGH_REPLICAS",),
            )
            stats = pc.stats()
            waits = stats["wait_barrier"].get("n", 0) - waits0
            requests = (
                stats["counters"].get("ingest_requests_coalesced", 0) - r0
            )
            out["quorum_keys_per_sec"] = round(q)
            out["quorum_write_requests"] = requests
            out["wait_barrier_observations"] = waits
            out["writes_per_barrier"] = round(requests / max(waits, 1), 2)
            assert waits < requests, (
                f"{waits} barrier waits for {requests} quorum write "
                f"requests — a coalesced flush must share ONE barrier "
                f"across its parked writes"
            )
            pc.close()
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
        for proc in procs:
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
    return out


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    print(json.dumps(run_load()))
