#!/usr/bin/env python
"""Config-3 streaming at scale (VERDICT r2 #6).

Part 1 — device-generated stream with checkpoints: insert >= 100M
device-generated keys into an m=2^30 blocked filter in B-key fused
steps (``--batch-log2``, default 4M — the value every r2-r5 artifact
row was measured at; pass 23 for the r5 bench-optimum 8M, which the
m=2^34 52.0M row in streaming_r5.json used), once without checkpoints
and once with the AsyncCheckpointer triggering every
``--ckpt-every-steps * B`` keys (default 8 steps; double-buffered HBM
snapshot + async D2H + background sink write). Reports the checkpoint-induced STALL on the
insert loop (the D2H itself rides the transfer engine and the writes a
background thread; only the HBM copy + scheduling contention can stall
inserts). Target: < 5%.

Part 2 — host-fed pack->H2D->insert with and without the pipeline's
prefetch overlap (background packing thread + early device_put). The
gain reported is the overlap's, bounded by H2D transport.

One JSON line per measurement; timings force host values.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from tpubloom import checkpoint as ckpt
from tpubloom.config import FilterConfig
from tpubloom.filter import BlockedBloomFilter, make_blocked_insert_fn
from tpubloom.parallel.pipeline import StreamInserter

_ap = argparse.ArgumentParser()
_ap.add_argument("--log2m", type=int, default=30)
_ap.add_argument("--total-mkeys", type=int, default=128)
_ap.add_argument("--ckpt-every-steps", type=int, default=8)
_ap.add_argument("--skip-host-fed", action="store_true")
_ap.add_argument("--batch-log2", type=int, default=22, help="device batch size (2^N keys); default 4M reproduces the r2-r5 artifact rows, 23 (=8M) is the r5 bench optimum")
_ap.add_argument(
    "--no-ckpt-only", action="store_true",
    help="run only the no-checkpoint device stream (the m=2^34 spec "
    "point: the 2 GiB filter fits this chip's HBM and streams at speed; "
    "snapshot stalls are bound by the device-to-host copy)",
)
_ARGS = _ap.parse_args()

LOG2M = _ARGS.log2m
B = 1 << _ARGS.batch_log2
TOTAL = _ARGS.total_mkeys * (1 << 20)
CKPT_EVERY_STEPS = _ARGS.ckpt_every_steps  # default 8 steps = 8 * B keys

config = FilterConfig(
    m=1 << LOG2M, k=7, key_len=16, block_bits=512, key_name="stream-bench"
)


def device_stream(with_checkpoints: bool, tmpdir: str) -> dict:
    from tpubloom.filter import blocked_storage_fat

    f = BlockedBloomFilter(config)
    # the class holds FAT storage since r4 — the raw insert fn must match
    insert = make_blocked_insert_fn(
        config, storage_fat=blocked_storage_fat(config)
    )
    lengths = jnp.full((B,), 16, jnp.int32)

    def step(state, seed):
        keys = jax.random.bits(jax.random.key(seed), (B, 16), jnp.uint8)
        return insert(state, keys, lengths)

    jit = jax.jit(step, donate_argnums=0)
    f.words = jit(f.words, 0)
    _ = int(np.asarray(f.words[0, 0]))  # compile + sync
    cp = None
    if with_checkpoints:
        cp = ckpt.AsyncCheckpointer(
            f, ckpt.FileSink(tmpdir), every_n_inserts=CKPT_EVERY_STEPS * B
        )
    steps = TOTAL // B
    t0 = time.perf_counter()
    for i in range(1, 1 + steps):
        f.words = jit(f.words, i)
        if cp:
            cp.notify_inserts(B)
    _ = int(np.asarray(f.words[0, 0]))
    dt = time.perf_counter() - t0
    written = 0
    flush_s = 0.0
    if cp:
        t1 = time.perf_counter()
        ok = cp.close(final_checkpoint=False)
        flush_s = time.perf_counter() - t1
        written = cp.checkpoints_written
        assert ok or written > 0, cp.last_error
    return {
        "keys": steps * B,
        "insert_loop_s": round(dt, 3),
        "keys_per_sec": round(steps * B / dt),
        "checkpoints_written": written,
        "final_flush_s": round(flush_s, 3),
    }


def host_fed(prefetch: int, n_keys: int = 1 << 21) -> dict:
    f = BlockedBloomFilter(config)
    rng = np.random.default_rng(0)
    # pre-generate raw key bytes so generation cost is not measured
    raw = [rng.bytes(16) for _ in range(n_keys)]
    ins = StreamInserter(f, batch_size=1 << 17, prefetch=prefetch)
    t0 = time.perf_counter()
    stats = ins.run(iter(raw))
    _ = int(np.asarray(f.words[0, 0]))
    dt = time.perf_counter() - t0
    return {
        "host_fed_keys": stats["inserted"],
        "prefetch": prefetch,
        "seconds": round(dt, 3),
        "keys_per_sec": round(stats["inserted"] / dt),
    }


def main():
    with tempfile.TemporaryDirectory() as tmp:
        shape = {"log2m": LOG2M, "total_keys": TOTAL,
                 "snapshot_mb": (1 << LOG2M) // 8 // (1 << 20),
                 "ckpt_every_keys": CKPT_EVERY_STEPS * B}
        print(json.dumps({"mode": "shape", **shape}), flush=True)
        base = device_stream(False, tmp)
        print(json.dumps({"mode": "device-stream no-ckpt", **base}), flush=True)
        if _ARGS.no_ckpt_only:
            return
        with_ck = device_stream(True, tmp)
        print(json.dumps({"mode": "device-stream ckpt", **with_ck}), flush=True)
        stall = (
            with_ck["insert_loop_s"] - base["insert_loop_s"]
        ) / base["insert_loop_s"]
        print(
            json.dumps(
                {
                    "mode": "checkpoint stall",
                    "stall_pct": round(100 * stall, 2),
                    "target_pct": 5.0,
                    "ok": stall < 0.05,
                }
            ),
            flush=True,
        )
    if not _ARGS.skip_host_fed:
        for pf in (0, 4):
            print(json.dumps({"mode": "host-fed", **host_fed(pf)}), flush=True)


if __name__ == "__main__":
    main()
