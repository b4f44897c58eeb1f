#!/usr/bin/env python
"""L4 transport-path throughput in ISOLATION (VERDICT r4 Missing #5).

Every prior e2e number rode a remote-TPU link whose H2D varied widely, so
the repo had no honest figure for what the gRPC+msgpack+service layer
itself costs. This measures it on loopback with a CPU-backend filter,
three layers deep so the costs separate:

  L0 filter-only    BlockedBloomFilter.insert_batch / include_batch
                    (the jitted CPU kernel work, no serialization)
  L1 +service       BloomService.InsertBatch(req dict) in-process
                    (adds msgpack encode/decode of the SAME batches)
  L2 +gRPC          BloomClient against a loopback grpc.Server
                    (adds HTTP/2 framing + socket + thread hop)

The transport overhead of interest is (L2 - L1) and the encode cost
(L1 - L0), reported per batch size. Single-core host: client and server
share the core, which is the honest worst case for loopback.

Run: JAX_PLATFORMS=cpu PYTHONPATH=/root/repo:$PYTHONPATH python benchmarks/grpc_path.py
Writes benchmarks/out/grpc_path_r5.json (one JSON object per line).
"""

from __future__ import annotations

import json
import os
import time

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from tpubloom.config import FilterConfig  # noqa: E402
from tpubloom.filter import BlockedBloomFilter  # noqa: E402
from tpubloom.server import protocol  # noqa: E402
from tpubloom.server.client import BloomClient  # noqa: E402
from tpubloom.server.service import BloomService, build_server  # noqa: E402

KEY_LEN = 16
BATCHES = (4_096, 65_536, 524_288)
REPS = {4_096: 16, 65_536: 8, 524_288: 4}

OUT_PATH = os.path.join(os.path.dirname(__file__), "out", "grpc_path_r5.json")
_rows = []


def emit(obj):
    print(json.dumps(obj), flush=True)
    _rows.append(obj)
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        for r in _rows:
            f.write(json.dumps(r) + "\n")


def _config():
    # m=2^24 blocked512: big enough that the sweep/scatter choice is the
    # normal one, small enough that CPU kernel time doesn't swamp L1-L0
    return FilterConfig(m=1 << 24, k=7, key_len=KEY_LEN, block_bits=512)


def _keys(rng, n):
    return [rng.bytes(KEY_LEN) for _ in range(n)]


def main():
    emit({
        "shape": {
            "m": 1 << 24, "k": 7, "key_len": KEY_LEN,
            "layers": ["L0 filter", "L1 +msgpack service", "L2 +gRPC loopback"],
            "platform": jax.default_backend(),
            "note": "single host core; client+server share it (honest loopback)",
        }
    })

    # L2 server (also hosts the L1 service object so state is comparable)
    service = BloomService()
    server, port = build_server(service, "127.0.0.1:0")
    server.start()
    client = BloomClient(f"127.0.0.1:{port}")
    client.wait_ready()

    rng = np.random.default_rng(7)
    for B in BATCHES:
        reps = REPS[B]
        keys = _keys(rng, B)
        payload_mb = B * KEY_LEN / 1e6

        # ---- L0: filter only ----
        f0 = BlockedBloomFilter(_config())
        f0.insert_batch(keys)  # warm the jit caches
        f0.include_batch(keys)
        t0 = time.perf_counter()
        for _ in range(reps):
            f0.insert_batch(keys)
        ins0 = (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        for _ in range(reps):
            f0.include_batch(keys)
        qry0 = (time.perf_counter() - t0) / reps

        # ---- L1: in-process service (msgpack encode/decode, no socket).
        # Requests are msgpack-encoded exactly as the wire would carry
        # them, then decoded by the service — protocol.dumps/loads is the
        # same codec _wrap uses.
        name1 = f"b{B}-l1"
        service.CreateFilter({
            "name": name1,
            "config": {
                "m": 1 << 24, "k": 7, "key_len": KEY_LEN, "block_bits": 512,
            },
        })
        req = protocol.encode({"name": name1, "keys": keys})
        service.InsertBatch(protocol.decode(req))  # warm
        protocol.encode(service.QueryBatch(protocol.decode(req)))
        t0 = time.perf_counter()
        for _ in range(reps):
            protocol.encode(service.InsertBatch(protocol.decode(req)))
        ins1 = (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        for _ in range(reps):
            protocol.encode(service.QueryBatch(protocol.decode(req)))
        qry1 = (time.perf_counter() - t0) / reps

        # ---- L2: full loopback RPC ----
        name2 = f"b{B}-l2"
        client.create_filter(
            name2,
            config={
                "m": 1 << 24, "k": 7, "key_len": KEY_LEN, "block_bits": 512,
            },
        )
        client.insert_batch(name2, keys)  # warm
        client.include_batch(name2, keys)
        t0 = time.perf_counter()
        for _ in range(reps):
            client.insert_batch(name2, keys)
        ins2 = (time.perf_counter() - t0) / reps
        t0 = time.perf_counter()
        for _ in range(reps):
            hits = client.include_batch(name2, keys)
        qry2 = (time.perf_counter() - t0) / reps
        assert bool(np.asarray(hits).all())

        emit({
            "batch": B,
            "payload_mb": round(payload_mb, 2),
            "insert_keys_per_sec": {
                "L0_filter": round(B / ins0),
                "L1_service": round(B / ins1),
                "L2_grpc": round(B / ins2),
            },
            "query_keys_per_sec": {
                "L0_filter": round(B / qry0),
                "L1_service": round(B / qry1),
                "L2_grpc": round(B / qry2),
            },
            "insert_overhead_ms": {
                "msgpack_service": round((ins1 - ins0) * 1e3, 2),
                "grpc_transport": round((ins2 - ins1) * 1e3, 2),
            },
            "query_overhead_ms": {
                "msgpack_service": round((qry1 - qry0) * 1e3, 2),
                "grpc_transport": round((qry2 - qry1) * 1e3, 2),
            },
            "l2_insert_mb_per_sec": round(payload_mb / ins2, 1),
            "reps": reps,
        })

    client.close()
    server.stop(grace=1)


if __name__ == "__main__":
    main()
