#!/usr/bin/env python
"""R (partition-size) sweep of the sweep kernel at the north-star shape.

The per-partition merge matmuls scale ~KMAX^2 * block_bits and keys per
partition ~lambda = B*R/n_blocks, so per-key MXU work shrinks with
lambda. This measures kernel-only rates for R in {128, 256, 512, 1024}
at B=4M to find the sweet spot (VERDICT r1 task 1 follow-up).
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tpubloom.config import FilterConfig
from tpubloom.ops import blocked
from tpubloom.ops.sweep import (
    _pack_positions,
    _stream_scaffold,
    _unpack_positions,
    choose_params,
    sweep_insert,
)

LOG2M = 32
B = 1 << 22
KEY_LEN = 16
STEPS = 8

config = FilterConfig(m=1 << LOG2M, k=7, key_len=KEY_LEN, block_bits=512)
NB, W, K, BB = config.n_blocks, config.words_per_block, config.k, config.block_bits
lengths = jnp.full((B,), KEY_LEN, jnp.int32)


def build_stream(keys, R, KMAX):
    P = NB // R
    blk, bit = blocked.block_positions(
        keys, lengths, n_blocks=NB, block_bits=BB, k=K, seed=config.seed,
        block_hash=config.block_hash,
    )
    blk = blk.astype(jnp.uint32)
    cols, nbits, packed = _pack_positions(bit, BB, K)
    idx0 = jnp.arange(1, B + 1, dtype=jnp.uint32)
    sorted_cols = lax.sort((blk,) + cols + (idx0,), num_keys=1)
    bs = sorted_cols[0].astype(jnp.int32)
    bit_sorted = _unpack_positions(sorted_cols[1:-1], BB, K, nbits, packed)
    masks = blocked.build_masks(bit_sorted, W)
    starts, upd = _stream_scaffold(bs, NB, P, R, KMAX)
    upd = upd.at[:B, 1 : W + 1].set(masks)
    upd = upd.at[:B, W + 1].set(sorted_cols[-1])
    return starts, upd


def main():
    import sys

    rng = np.random.default_rng(0)
    keys = jax.device_put(rng.integers(0, 256, (B, KEY_LEN), np.uint8))
    # R values from argv (one fresh process per measurement)
    r_list = tuple(int(a) for a in sys.argv[1:]) or (128, 256, 512, 1024)
    for R in r_list:
        lam = B // (NB // R)
        _, KMAX = choose_params(NB, B, R=R)
        try:
            starts, upd = jax.jit(lambda k: build_stream(k, R, KMAX))(keys)
            starts.block_until_ready()

            for pres in (True, False):
                def step(state, upd, starts):
                    out = sweep_insert(
                        state, upd, starts, R=R, KMAX=KMAX,
                        interpret=False, with_presence=pres,
                    )
                    if pres:
                        nb2, presb = out
                        return nb2, jnp.sum(presb, dtype=jnp.uint32)
                    return out, jnp.sum(out[:: NB // 64], dtype=jnp.uint32)

                jit = jax.jit(step, donate_argnums=(0,))
                state = jnp.zeros((NB, W), jnp.uint32)
                t0 = time.perf_counter()
                state, carry = jit(state, upd, starts)
                carry.block_until_ready()
                compile_s = time.perf_counter() - t0
                t0 = time.perf_counter()
                for _ in range(STEPS):
                    state, carry = jit(state, upd, starts)
                carry.block_until_ready()
                dt = (time.perf_counter() - t0) / STEPS
                print(
                    json.dumps(
                        {
                            "R": R, "KMAX": KMAX, "lambda": lam,
                            "with_presence": pres,
                            "ms": round(dt * 1e3, 3),
                            "ns_per_key": round(dt / B * 1e9, 3),
                            "keys_per_sec": round(B / dt),
                            "compile_s": round(compile_s, 1),
                        }
                    ),
                    flush=True,
                )
            del state, carry, starts, upd
        except Exception as e:
            print(json.dumps({"R": R, "error": repr(e)[:300]}), flush=True)


if __name__ == "__main__":
    main()
