#!/usr/bin/env python
"""Round-5 probes: query-path decomposition, unsort-gather, radix kill data.

Three questions this answers on hardware (VERDICT r4 Missing #2/#3,
Weak #4), all at the north-star shape (m=2^32, k=7, blocked512 fat,
B=4M):

1. WHERE does the 28.7M keys/s query rate go? Cumulative prefixes of
   the gather-query path: keygen -> +hash -> +masks+fold -> +gather ->
   full compare. The gather of [B] 512-byte fat rows from the 512 MB
   array is the suspected floor (random HBM reads).
2. Can the presence unsort's first stage be a GATHER? The kernel's
   slot-tile verdicts live at host-computable flat offsets; if a 1-D
   ``flat[idx]`` take of B elements is fast, the unsort becomes
   take + one B-sized single-column sort instead of one 2.1x-larger
   slot sort.
3. Radix-sort kill data (VERDICT r4 #2): a TPU radix/bucket sort needs
   data-dependent PLACEMENT. The three known mechanisms are measured
   here against ``lax.sort``: XLA row scatter (~100 ns/row documented),
   1-D take-based permutation apply, and the sort itself at both the
   B=4M (front sort) and slot-count (unsort) sizes. Pallas-side
   placement via dynamic per-element DMA is already dead: r4 measured
   +86% kernel time from a dynamic DMA loop at ZERO iterations
   (benchmarks/RESULTS_r4.md §5, dma_ablate).

Timing: TO-VALUE (int(np.asarray(carry)) after a chained loop) — bur
can lie on this stack (benchmarks/RESULTS_r3.md §1).
Run: PYTHONPATH=/root/repo:$PYTHONPATH timeout 1800 python benchmarks/query_probe.py
Writes benchmarks/out/query_probe_r5.json (one JSON object per line).
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpubloom.config import FilterConfig
from tpubloom.ops import blocked

LOG2M = 32
B = 1 << 22
KEY_LEN = 16
STEPS = 12

config = FilterConfig(m=1 << LOG2M, k=7, key_len=KEY_LEN, block_bits=512)
NB, W, K, BB = config.n_blocks, config.words_per_block, config.k, config.block_bits
J = 128 // W
NBJ = NB // J
FAT_SHAPE = (NBJ, 128)
lengths = jnp.full((B,), KEY_LEN, jnp.int32)

OUT_PATH = os.path.join(os.path.dirname(__file__), "out", "query_probe_r5.json")
_rows = []


def emit(obj):
    print(json.dumps(obj), flush=True)
    _rows.append(obj)


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def keygen(carry, i):
    return jax.random.bits(
        jax.random.key(i ^ (carry & 0xFFFF)), (B, KEY_LEN), jnp.uint8
    )


def _positions(keys):
    return blocked.block_positions(
        keys, lengths, n_blocks=NB, block_bits=BB, k=K, seed=config.seed,
        block_hash=config.block_hash,
    )


def run(name, step, *operands, steps=STEPS, extra=None):
    """Chained to-value loop over ``step(carry, i, *operands) -> carry``.

    Large arrays MUST ride as ``operands``: a closed-over device array
    becomes an HLO constant baked into the program."""
    jit = jax.jit(step)
    carry = jit(_u32(0), 0, *operands)
    int(np.asarray(carry))
    carry = jit(carry, 1, *operands)
    int(np.asarray(carry))
    t0 = time.perf_counter()
    for i in range(2, 2 + steps):
        carry = jit(carry, i, *operands)
    int(np.asarray(carry))
    dt = (time.perf_counter() - t0) / steps
    row = {
        "stage": name,
        "ms_per_step": round(dt * 1e3, 3),
        "ns_per_key": round(dt / B * 1e9, 3),
    }
    if extra:
        row.update(extra)
    emit(row)
    return dt


def main():
    emit({
        "shape": {
            "m": config.m, "k": K, "B": B, "block_bits": BB, "n_blocks": NB,
            "J": J, "NBJ": NBJ,
            "platform": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "timing": "to-value (int(np.asarray(carry)) after chained loop)",
        }
    })

    # a ~6%-fill fat array (north-star operating point) so compares see
    # realistic bit density; contents do not affect gather/compare cost
    fill = jax.random.bits(jax.random.key(99), FAT_SHAPE, jnp.uint32)
    fat = jnp.asarray(fill & fill >> 1 & fill >> 2 & fill >> 3 & _u32(0x11111111))

    # ---- 1. query-path decomposition (cumulative prefixes) ----
    def q0(carry, i):
        keys = keygen(carry, i)
        return jnp.sum(keys, dtype=jnp.uint32)

    def q1(carry, i):
        keys = keygen(carry, i)
        blk, bit = _positions(keys)
        return jnp.sum(blk.astype(jnp.uint32)) + jnp.sum(bit)

    def q2(carry, i):
        keys = keygen(carry, i)
        blk, bit = _positions(keys)
        masks = blocked.build_masks(bit, W)
        return jnp.sum(masks) + jnp.sum(blk.astype(jnp.uint32))

    def q3(carry, i):
        keys = keygen(carry, i)
        blk, bit = _positions(keys)
        masks = blocked.build_masks(bit, W)
        frow, m128 = blocked.fat_fold_masks(blk, masks, J)
        return jnp.sum(m128) + jnp.sum(frow.astype(jnp.uint32))

    def q4(carry, i, fat):
        keys = keygen(carry, i)
        blk, bit = _positions(keys)
        masks = blocked.build_masks(bit, W)
        frow, m128 = blocked.fat_fold_masks(blk, masks, J)
        rows128 = fat[frow]
        # reduce ALL 128 lanes: summing one column would let XLA fold the
        # slice into the gather and narrow the 512B-row fetch to 4B/row
        return jnp.sum(rows128, dtype=jnp.uint32) + jnp.sum(m128[:, 0])

    def q5(carry, i, fat):
        keys = keygen(carry, i)
        blk, bit = _positions(keys)
        masks = blocked.build_masks(bit, W)
        hits = blocked.fat_blocked_query(fat, blk, masks)
        return jnp.sum(hits.astype(jnp.uint32))

    prev = 0.0
    deltas = {}
    for name, fn in [
        ("q0 keygen", q0),
        ("q1 +hash", q1),
        ("q2 +masks", q2),
        ("q3 +fold", q3),
        ("q4 +gather", q4),
        ("q5 full query", q5),
    ]:
        ops = (fat,) if name in ("q4 +gather", "q5 full query") else ()
        dt = run(name, fn, *ops)
        deltas[name] = dt - prev
        prev = dt
    emit({
        "query_deltas_ms": {k: round(v * 1e3, 3) for k, v in deltas.items()},
        "query_keys_per_sec": round(B / prev),
    })

    # gather in ISOLATION (no hash chain): random fat-row gather + touch
    def g_only(carry, i, fat):
        h = jax.random.bits(
            jax.random.key(i ^ (carry & 0xFFFF)), (B,), jnp.uint32
        )
        frow = (h & _u32(NBJ - 1)).astype(jnp.int32)
        rows = fat[frow]
        # full-row reduce pins the gather at its real 512B/row width
        return jnp.sum(rows, dtype=jnp.uint32)

    run("gather_only [B] x 512B fat rows", g_only, fat,
        extra={"bytes_gathered": B * 512})

    # compare in ISOLATION: rows already gathered, fold + compare only
    rows_pre = jax.device_put(
        np.random.default_rng(1).integers(0, 2**32, (B, 128), np.uint32).astype(
            np.uint32
        )
    )

    def c_only(carry, i, rows_pre):
        keys = keygen(carry, i)
        blk, bit = _positions(keys)
        masks = blocked.build_masks(bit, W)
        _, m128 = blocked.fat_fold_masks(blk, masks, J)
        r = rows_pre | carry
        return jnp.sum(
            jnp.all((r & m128) == m128, axis=-1).astype(jnp.uint32)
        )

    run("compare_only (hash+masks+fold+allcmp, no gather)", c_only, rows_pre)

    # ---- 2. unsort-gather probes ----
    flat_src = jax.random.bits(jax.random.key(5), (4 * B,), jnp.uint32)

    def take1d(carry, i, flat_src):
        idx = (
            jax.random.bits(jax.random.key(i ^ (carry & 0xFFFF)), (B,), jnp.uint32)
            & _u32(4 * B - 1)
        ).astype(jnp.int32)
        return jnp.sum(flat_src[idx])

    run("take1d: flat[idx] B from 16.8M u32", take1d, flat_src)

    # ---- 3. radix kill data ----
    def scatter_rows(carry, i):
        idx = (
            jax.random.bits(jax.random.key(i ^ (carry & 0xFFFF)), (B,), jnp.uint32)
            & _u32(B - 1)
        ).astype(jnp.int32)
        v = idx.astype(jnp.uint32) ^ carry
        out = jnp.zeros((B,), jnp.uint32).at[idx].set(v)
        return jnp.sum(out)

    run("scatter: zeros(B).at[idx].set (4M u32)", scatter_rows, steps=4)

    def sort1(carry, i, src):
        (s,) = lax.sort((src ^ carry,), num_keys=1)
        return jnp.sum(s)

    for n, lab in [(B, "4M"), (2 * B, "8.4M-ish")]:
        src = jax.random.bits(jax.random.key(11), (n,), jnp.uint32)
        run(f"lax.sort 1 u32 col, n={lab}", sort1, src)

    def sort4(carry, i, s0, s1, s2, s3):
        out = lax.sort((s0 ^ carry, s1, s2, s3), num_keys=1)
        return sum(jnp.sum(c) for c in out).astype(jnp.uint32)

    src4 = [
        jax.random.bits(jax.random.fold_in(jax.random.key(13), i), (B,), jnp.uint32)
        for i in range(4)
    ]
    run("lax.sort 4 u32 cols, n=4M", sort4, *src4)

    # histogram via one-hot matmul (the radix COUNT pass, for the record:
    # counting is cheap — placement is what kills the radix sort)
    def hist_mm(carry, i):
        h = jax.random.bits(
            jax.random.key(i ^ (carry & 0xFFFF)), (B,), jnp.uint32
        )
        b = (h & _u32(255)).astype(jnp.int32).reshape(-1, 512)
        oh = jnp.where(
            b[:, :, None] == jnp.arange(256, dtype=jnp.int32)[None, None, :],
            jnp.float32(1), jnp.float32(0),
        ).astype(jnp.bfloat16)
        cnt = jnp.sum(
            lax.dot_general(
                jnp.ones((b.shape[0], 512), jnp.bfloat16), oh,
                (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ),
            axis=0,
        )
        return jnp.sum(cnt).astype(jnp.uint32)

    run("radix hist: 8-bit one-hot matmul counts", hist_mm, steps=4)

    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        for r in _rows:
            f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
