#!/usr/bin/env python
"""sweep2 prototype — HISTORICAL (round-3 evidence; superseded by the
pack-4 fat kernel in tpubloom/ops/sweep.py — do not use for current
numbers, see benchmarks/RESULTS_r4.md).

Acting on the round-2/3 ablation data (VERDICT r3 #1).

Measured facts at the north-star shape (B=4M, m=2^32, bb=512, R=512,
KMAX=384, P=16384) from kernel_ablate on this chip:

  A stream-only   46.6ms  -> ~90M keys/s ceiling of the CURRENT structure
  C merge-free    72.6ms  (delta == shipping kernel, bit-identical)
  D shipping      76.6ms

so ~60% of the kernel is the A-floor (grid steps + update-stream DMA),
and the merge machinery costs ~4ms once the delta is merge-free. The
attacks, each a flag here so their contribution is measured separately:

  * narrow update rows: [Btot, 32] lanes instead of [Btot, 128] — the
    stream only carries block id + W mask words + idx = 18 words, so
    128 lanes is 7x DMA waste (2GB/batch instead of 0.5GB).
  * big grid tiles + sub-tiles: R_dma rows per grid step (fewer steps,
    one big window DMA per step) while the one-hot placement matmul
    keeps its own R_sub granularity (total MACs = NB*bb*KMAX_sub do
    NOT grow with R_dma) via dynamic sublane slices of the window.
  * int8 MXU for the placement matmul (operands are 0/1; v5e runs int8
    at 2x bf16 rate).

Insert-only (no presence), no overflow-chunk loop: the host asserts no
sub-window overflows its KMAX_sub fetch window (uniform benchmark keys;
the production port keeps the chunk loop). Every variant's final state
is checked bit-identical (sampled) to the shipping sweep kernel.

Run: PYTHONPATH=/root/repo:$PYTHONPATH python benchmarks/sweep2_proto.py
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpubloom.config import FilterConfig
from tpubloom.ops import blocked
from tpubloom.ops.sweep import (
    _ALIGN,
    _pack_positions,
    _stream_scaffold,
    _unpack_positions,
    choose_params,
    sweep_insert,
)

LOG2M = 32
B = 1 << 22
KEY_LEN = 16
STEPS = 32

config = FilterConfig(m=1 << LOG2M, k=7, key_len=KEY_LEN, block_bits=512)
NB, W, K, BB = config.n_blocks, config.words_per_block, config.k, config.block_bits
lengths = jnp.full((B,), KEY_LEN, jnp.int32)


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def _delta_merge_free(sub, base, R_SUB, KMAX, W, int8: bool, oh_f32=None,
                      bits=None):
    """uint32[R_SUB, W] OR-delta of update window ``sub`` ([KMAX, LANES]:
    col 0 block id, cols 1..W masks) against rows [base, base+R_SUB).
    ``oh_f32``/``bits`` let callers share the one-hot row match and the
    mask bit-plane expansion."""
    if oh_f32 is None:
        rl = (sub[:, 0:1] - base).astype(jnp.int32)
        colsR = lax.broadcasted_iota(jnp.int32, (KMAX, R_SUB), 1)
        oh_f32 = jnp.where(rl == colsR, jnp.float32(1), jnp.float32(0))
    if bits is None:
        m = sub[:, 1 : W + 1]
        colC = lax.broadcasted_iota(jnp.int32, (KMAX, W * 32), 1)
        rep = jnp.concatenate([m] * 32, axis=1)
        bits = (rep >> (colC // W).astype(jnp.uint32)) & _u32(1)
    if int8:
        oh = oh_f32.astype(jnp.int8)
        bits8 = bits.astype(jnp.int8)
        cnt = lax.dot_general(
            oh, bits8, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # [R_SUB, W*32]
        present = jnp.where(cnt > 0, jnp.float32(1), jnp.float32(0)).astype(
            jnp.bfloat16
        )
    else:
        oh = oh_f32.astype(jnp.bfloat16)
        bitsf = bits.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
        cnt = lax.dot_general(
            oh, bitsf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        present = jnp.where(cnt > 0, jnp.float32(1), jnp.float32(0)).astype(
            jnp.bfloat16
        )
    # pack 512 bit-planes -> 4W 8-bit quarters -> W u32 words (all exact)
    ccol = lax.broadcasted_iota(jnp.int32, (W * 32, 4 * W), 0)
    hcol = lax.broadcasted_iota(jnp.int32, (W * 32, 4 * W), 1)
    b_of_c = ccol // W
    w_of_c = lax.rem(ccol, W)
    pack_w = jnp.where(
        (w_of_c + (b_of_c // 8) * W) == hcol,
        (1 << lax.rem(b_of_c, 8)).astype(jnp.float32),
        jnp.float32(0),
    ).astype(jnp.bfloat16)
    quarters = lax.dot_general(
        present, pack_w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.bfloat16)
    qcol = lax.broadcasted_iota(jnp.int32, (4 * W, W), 0)
    wcol = lax.broadcasted_iota(jnp.int32, (4 * W, W), 1)
    q_of = qcol // W
    w_of = lax.rem(qcol, W)
    comb_lo = jnp.where(
        (w_of == wcol) & (q_of < 2),
        jnp.where(q_of == 0, jnp.float32(1), jnp.float32(256)),
        jnp.float32(0),
    ).astype(jnp.bfloat16)
    comb_hi = jnp.where(
        (w_of == wcol) & (q_of >= 2),
        jnp.where(q_of == 2, jnp.float32(1), jnp.float32(256)),
        jnp.float32(0),
    ).astype(jnp.bfloat16)
    lo = lax.dot_general(
        quarters, comb_lo, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    hi = lax.dot_general(
        quarters, comb_hi, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return lo.astype(jnp.int32).astype(jnp.uint32) | (
        hi.astype(jnp.int32).astype(jnp.uint32) << _u32(16)
    )


def _presence_of(sub, oh_f32, tile, m, KMAX, W):
    """f32[KMAX, 1] pre-update membership of each slot: extract the slot's
    OLD row one 8-bit quarter at a time (bf16-exact) and test
    (row & mask) == mask across all W words."""
    oh = oh_f32.astype(jnp.bfloat16)
    acc_ok = None
    for q in range(4):
        tq = (
            ((tile >> _u32(8 * q)) & _u32(0xFF))
            .astype(jnp.int32)
            .astype(jnp.float32)
            .astype(jnp.bfloat16)
        )
        rq = lax.dot_general(
            oh, tq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        rq_u = rq.astype(jnp.int32).astype(jnp.uint32)
        mq = (m >> _u32(8 * q)) & _u32(0xFF)
        ok = jnp.where((mq & rq_u) == mq, jnp.float32(1), jnp.float32(0))
        acc_ok = ok if acc_ok is None else acc_ok * ok
    return jnp.min(acc_ok, axis=1, keepdims=True)


def _pack_pres(v, KMAX, LANES_OUT=128):
    """[KMAX, 1] u32 slot values -> [8, LANES_OUT] tile via 4 exact byte
    matmuls (slot j at (j % 8, j // 8); columns >= KMAX//8 are zero
    padding so the output block stays 128-lane aligned — a 48-lane
    output block measurably serializes the out stream). Mosaic has no
    sublane->lane reshape, hence the matmuls."""
    jj8 = lax.broadcasted_iota(jnp.int32, (KMAX, 8), 0)
    aa8 = lax.broadcasted_iota(jnp.int32, (KMAX, 8), 1)
    oh_a = jnp.where(jj8 % 8 == aa8, jnp.float32(1), jnp.float32(0))
    jjc = lax.broadcasted_iota(jnp.int32, (KMAX, LANES_OUT), 0)
    ccc = lax.broadcasted_iota(jnp.int32, (KMAX, LANES_OUT), 1)
    oh_b = jnp.where(jjc // 8 == ccc, jnp.float32(1), jnp.float32(0)).astype(
        jnp.bfloat16
    )
    pres = jnp.zeros((8, LANES_OUT), jnp.uint32)
    for q in range(4):
        vb = ((v >> _u32(8 * q)) & _u32(0xFF)).astype(jnp.int32).astype(
            jnp.float32
        )
        left = (oh_a * vb).astype(jnp.bfloat16)
        outq = lax.dot_general(
            left, oh_b, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        pres = pres | (outq.astype(jnp.int32).astype(jnp.uint32) << _u32(8 * q))
    return pres


def _expand_bits(m, KMAX, W):
    """[KMAX, W] packed words -> [KMAX, W*32] 0/1 bit-planes, b-major
    (column c = b*W + w holds bit b of word w)."""
    colC = lax.broadcasted_iota(jnp.int32, (KMAX, W * 32), 1)
    rep = jnp.concatenate([m] * 32, axis=1)
    return (rep >> (colC // W).astype(jnp.uint32)) & _u32(1)


def _kernel2(
    starts_ref,  # SMEM [P_sub + 1] i32
    upd_ref,  # ANY [Btot, LANES]
    blocks_ref,  # VMEM [R_DMA, W]
    *rest,  # out_ref [, pres_ref], sup_ref, sems
    R_SUB: int,
    S: int,
    KMAX_SUB: int,
    KMAX_BIG: int,
    W: int,
    INT8: bool,
    LEVEL: str = "full",  # "A" stream only | "B" +onehot+bits | "full"
    PRES: bool = False,
    PRESV3: bool = False,
):
    if PRES:
        out_ref, pres_ref, sup_ref, sems = rest
    else:
        out_ref, sup_ref, sems = rest
        pres_ref = None
    p = pl.program_id(0)
    num_p = pl.num_programs(0)

    def off_big(pp):
        return (starts_ref[pp * S] // _ALIGN) * _ALIGN

    def fetch(slot, pp):
        pltpu.make_async_copy(
            upd_ref.at[pl.ds(off_big(pp), KMAX_BIG), :],
            sup_ref.at[slot],
            sems.at[slot],
        ).start()

    def wait(slot):
        pltpu.make_async_copy(
            upd_ref.at[pl.ds(0, KMAX_BIG), :], sup_ref.at[slot], sems.at[slot]
        ).wait()

    slot = lax.rem(p, 2)

    @pl.when(p == 0)
    def _():
        fetch(0, 0)

    @pl.when(p + 1 < num_p)
    def _():
        fetch(1 - slot, p + 1)

    wait(slot)
    if LEVEL == "A":
        row = sup_ref[slot, 0:1, 1 : W + 1]
        out_ref[:] = blocks_ref[:] | (row * _u32(0))
        return
    o_big = off_big(p)
    pres_acc = (
        jnp.zeros((KMAX_SUB, 128), jnp.uint32) if (PRES and PRESV3) else None
    )
    for t in range(S):
        q = p * S + t
        rel = (starts_ref[q] // _ALIGN) * _ALIGN - o_big
        sub = sup_ref[slot, pl.ds(rel, KMAX_SUB), :]
        base = (_u32(p) * _u32(S * R_SUB)) + _u32(t * R_SUB)
        sl = pl.ds(t * R_SUB, R_SUB)
        if LEVEL == "B":
            rl = (sub[:, 0:1] - base).astype(jnp.int32)
            colsR = lax.broadcasted_iota(jnp.int32, (KMAX_SUB, R_SUB), 1)
            m = sub[:, 1 : W + 1]
            colC = lax.broadcasted_iota(jnp.int32, (KMAX_SUB, W * 32), 1)
            rep = jnp.concatenate([m] * 32, axis=1)
            bits = (rep >> (colC // W).astype(jnp.uint32)) & _u32(1)
            oh = jnp.where(rl == colsR, jnp.float32(1), jnp.float32(0))
            cheap = jnp.min(oh, axis=1, keepdims=True) + jnp.min(
                bits.astype(jnp.int32).astype(jnp.float32), axis=1, keepdims=True
            )
            out_ref[sl, :] = blocks_ref[sl, :] | (
                cheap.astype(jnp.int32).astype(jnp.uint32) * _u32(0)
            )
            continue
        rl = (sub[:, 0:1] - base).astype(jnp.int32)
        colsR = lax.broadcasted_iota(jnp.int32, (KMAX_SUB, R_SUB), 1)
        oh_f32 = jnp.where(rl == colsR, jnp.float32(1), jnp.float32(0))
        bits0 = _expand_bits(sub[:, 1 : W + 1], KMAX_SUB, W) if (
            PRES and PRESV3
        ) else None
        delta = _delta_merge_free(sub, base, R_SUB, KMAX_SUB, W, INT8,
                                  oh_f32=oh_f32, bits=bits0)
        if PRES and PRESV3:
            # presence without per-slot extraction matmuls: ONE big int8
            # matmul projects each slot's OLD row bits (oh @ tilebits),
            # then VPU row-sums decide all-mask-bits-present. The 8
            # small matmuls of the v1 scheme cost ~50ms/pass in launch
            # overhead; this is 1 launch + VPU.
            bits = bits0
            tilebits = _expand_bits(blocks_ref[sl, :], R_SUB, W)
            proj = lax.dot_general(
                oh_f32.astype(jnp.int8), tilebits.astype(jnp.int8),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # [KMAX, 512] old-row bits per slot (0/1)
            bi = bits.astype(jnp.int32)
            hit = jnp.sum(bi * proj, axis=1, keepdims=True)
            npos = jnp.sum(bi, axis=1, keepdims=True)
            idxp1 = sub[:, W + 1 : W + 2]
            a_q = o_big + rel
            ipos = lax.broadcasted_iota(jnp.int32, (KMAX_SUB, 1), 0) + a_q
            real = (
                (ipos >= starts_ref[q]) & (ipos < starts_ref[q + 1]) & (idxp1 > 0)
            )
            hbit = jnp.where(hit == npos, _u32(0x80000000), _u32(0))
            v = jnp.where(real, idxp1 | hbit, _u32(0))
            # slot values ride column t of the per-step [KMAX, 128] tile
            colp = lax.broadcasted_iota(jnp.int32, (KMAX_SUB, 128), 1)
            pres_acc = pres_acc | jnp.where(colp == t, v, _u32(0))
        elif PRES:
            m = sub[:, 1 : W + 1]
            hit0 = _presence_of(sub, oh_f32, blocks_ref[sl, :], m, KMAX_SUB, W)
            idxp1 = sub[:, W + 1 : W + 2]
            a_q = o_big + rel
            ipos = lax.broadcasted_iota(jnp.int32, (KMAX_SUB, 1), 0) + a_q
            real = (
                (ipos >= starts_ref[q]) & (ipos < starts_ref[q + 1]) & (idxp1 > 0)
            )
            hbit = jnp.where(hit0 > 0.5, _u32(0x80000000), _u32(0))
            v = jnp.where(real, idxp1 | hbit, _u32(0))
            pres_ref[pl.ds(t * 8, 8), :] = _pack_pres(v, KMAX_SUB)
        out_ref[sl, :] = blocks_ref[sl, :] | delta
    if PRES and PRESV3:
        pres_ref[:] = pres_acc


def sweep2_insert(
    blocks, upd, starts, *, R_SUB, S, KMAX_SUB, KMAX_BIG, INT8,
    LEVEL="full", PRES=False, PRESV3=False,
):
    NB_, W_ = blocks.shape
    R_DMA = R_SUB * S
    P = NB_ // R_DMA
    LANES = upd.shape[1]
    out_shape = jax.ShapeDtypeStruct((NB_, W_), jnp.uint32)
    out_spec = pl.BlockSpec((R_DMA, W_), lambda p, *_: (p, 0))
    if PRES and PRESV3:
        # per-step [KMAX_SUB, 128] tile: slot j of sub-tile t at (j, t)
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct((P * KMAX_SUB, 128), jnp.uint32),
        )
        out_spec = (
            out_spec,
            pl.BlockSpec((KMAX_SUB, 128), lambda p, *_: (p, 0)),
        )
    elif PRES:
        # 128-lane-padded presence tiles (slots live in cols < KMAX_SUB//8)
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct((P * S * 8, 128), jnp.uint32),
        )
        out_spec = (
            out_spec,
            pl.BlockSpec((S * 8, 128), lambda p, *_: (p, 0)),
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((R_DMA, W_), lambda p, *_: (p, 0)),
        ],
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((2, KMAX_BIG, LANES), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(
            _kernel2,
            R_SUB=R_SUB, S=S, KMAX_SUB=KMAX_SUB, KMAX_BIG=KMAX_BIG,
            W=W_, INT8=INT8, LEVEL=LEVEL, PRES=PRES, PRESV3=PRESV3,
        ),
        out_shape=out_shape,
        grid_spec=grid_spec,
        input_output_aliases={2: 0},
    )
    return fn(starts, upd, blocks)


def build_stream(keys, R_sub, KMAX_big, lanes):
    """Sorted update stream (with idx column) + R_sub-granular partition
    boundaries."""
    P_sub = NB // R_sub
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
    starts = jnp.searchsorted(
        bs, (jnp.arange(P_sub + 1, dtype=jnp.int32) * R_sub).astype(jnp.int32)
    ).astype(jnp.int32)
    pad = KMAX_big + _ALIGN
    upd = jnp.zeros((B + pad, lanes), jnp.uint32)
    upd = upd.at[:, 0].set(
        jnp.concatenate(
            [bs.astype(jnp.uint32), jnp.full((pad,), NB, jnp.uint32)]
        )
    )
    upd = upd.at[:B, 1 : W + 1].set(masks)
    upd = upd.at[:B, W + 1].set(sorted_cols[-1])
    return starts, upd


def check_windows(starts, S, KMAX_sub, KMAX_big):
    """No sub-window or big window may overflow its fetch (proto-only:
    the production port keeps the overflow chunk loop instead)."""
    s = np.asarray(starts).astype(np.int64)
    P_sub = len(s) - 1
    a = (s[:-1] // _ALIGN) * _ALIGN  # aligned sub-window starts
    sub_span = s[1:] - a  # rows each sub-window must cover
    o_big = np.repeat((s[0:P_sub:S] // _ALIGN) * _ALIGN, S)
    big_need = a + KMAX_sub - o_big  # KMAX_sub rows are read at offset a
    return int(sub_span.max()), int(big_need.max())


def run_variant(name, starts, upd, *, R_SUB, S, KMAX_SUB, KMAX_BIG, INT8,
                ref_state=None, LEVEL="full", PRES=False, PRESV3=False):
    def step(state, upd, starts):
        out = sweep2_insert(
            state, upd, starts,
            R_SUB=R_SUB, S=S, KMAX_SUB=KMAX_SUB, KMAX_BIG=KMAX_BIG, INT8=INT8,
            LEVEL=LEVEL, PRES=PRES, PRESV3=PRESV3,
        )
        if PRES:
            out, presb = out
            return out, jnp.sum(out[:: NB // 64], dtype=jnp.uint32) + jnp.sum(
                presb[:: max(1, presb.shape[0] // 64)], dtype=jnp.uint32
            )
        return out, jnp.sum(out[:: NB // 64], dtype=jnp.uint32)

    jit = jax.jit(step, donate_argnums=(0,))
    state = jnp.zeros((NB, W), jnp.uint32)
    t0 = time.perf_counter()
    state, carry = jit(state, upd, starts)
    _ = int(np.asarray(carry))  # force a host value: bur alone can LIE here
    compile_s = time.perf_counter() - t0
    ok = None
    if ref_state is not None:
        ok = bool(
            jnp.array_equal(state[:: NB // 4096], ref_state[:: NB // 4096])
        ) and bool(
            jnp.array_equal(state[1 :: NB // 1024], ref_state[1 :: NB // 1024])
        )
    # TIMING RECIPE: a long chained loop forced to a HOST VALUE; the
    # first to-value sync carries a one-time cost, so steps must
    # amortize it.
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, carry = jit(state, upd, starts)
    carry.block_until_ready()
    bur_dt = (time.perf_counter() - t0) / STEPS
    _ = int(np.asarray(carry))
    dt = (time.perf_counter() - t0) / STEPS
    P = NB // (R_SUB * S)
    implausible = (2 * NB * W * 4 / dt) > 900e9
    print(
        json.dumps(
            {
                "variant": name,
                "timing_implausible": implausible,
                "bur_ms": round(bur_dt * 1e3, 3),
                "R_sub": R_SUB, "S": S, "KMAX_sub": KMAX_SUB,
                "KMAX_big": KMAX_BIG, "lanes": int(upd.shape[1]),
                "int8": INT8, "grid": P,
                "ms": round(dt * 1e3, 3),
                "us_per_grid_step": round(dt / P * 1e6, 3),
                "keys_per_sec": round(B / dt),
                "compile_s": round(compile_s, 1),
                "first_pass_matches_shipping": ok,
            }
        ),
        flush=True,
    )
    del state


def main():
    rng = np.random.default_rng(0)
    keys = jax.device_put(rng.integers(0, 256, (B, KEY_LEN), np.uint8))

    # reference final state: ONE pass of the shipping kernel on the same keys
    R0, KMAX0 = choose_params(NB, B)
    blk, bit = blocked.block_positions(
        keys, lengths, n_blocks=NB, block_bits=BB, k=K, seed=config.seed,
        block_hash=config.block_hash,
    )
    from tpubloom.ops.sweep import apply_blocked_updates

    ref_state = jax.jit(
        lambda b, bl, bi: apply_blocked_updates(
            b, bl, bi, jnp.ones((B,), bool), block_bits=BB, interpret=False
        )
    )(jnp.zeros((NB, W), jnp.uint32), blk, bit)
    ref_state.block_until_ready()

    # lanes are pinned to 128: Mosaic rejects DMA slices whose lane dim is
    # not 128-aligned ("Slice shape along dimension 1 must be aligned to
    # tiling (128), but is 32" — measured 2026-07-30), so a [Btot, 32]
    # stream cannot be window-fetched. The A-floor is per-grid-step
    # overhead, not bytes, so wide rows + big S is the attack.
    variants = [
        # (name, R_sub, S, lanes, int8, level, pres, presv3)
        ("S8 int8 presV3", 512, 8, 128, True, "full", True, True),
        ("S4 int8 presV3", 512, 4, 128, True, "full", True, True),
        ("S8 R256 int8 presV3", 256, 8, 128, True, "full", True, True),
        ("S16 int8 presV3", 512, 16, 128, True, "full", True, True),
    ]
    built = {}
    for name, r_sub, s, lanes, int8, level, pres, presv3 in variants:
        lam_sub = B * r_sub // NB
        KMAX_sub = min(1024, max(16, (lam_sub + max(16, int(8 * lam_sub**0.5)) + 7) // 8 * 8))
        lam_big = lam_sub * s
        KMAX_big = (
            KMAX_sub if s == 1
            else ((lam_big + KMAX_sub + 256 + 7) // 8) * 8
        )
        key_ = (r_sub, KMAX_big, lanes)
        if key_ not in built:
            starts, upd = jax.jit(
                lambda kk: build_stream(kk, r_sub, KMAX_big, lanes)
            )(keys)
            starts.block_until_ready()
            built[key_] = (starts, upd)
        starts, upd = built[key_]
        sub_max, big_need = check_windows(starts, s, KMAX_sub, KMAX_big)
        if sub_max > KMAX_sub or big_need > KMAX_big:
            print(json.dumps({"variant": name, "skip": "window overflow",
                              "sub_max": sub_max, "big_need": big_need}),
                  flush=True)
            continue
        try:
            run_variant(
                name, starts, upd,
                R_SUB=r_sub, S=s, KMAX_SUB=KMAX_sub, KMAX_BIG=KMAX_big,
                INT8=int8, ref_state=ref_state if level == "full" else None,
                LEVEL=level, PRES=pres, PRESV3=presv3,
            )
        except Exception as e:
            print(json.dumps({"variant": name, "error": repr(e)[:400]}),
                  flush=True)


if __name__ == "__main__":
    main()
