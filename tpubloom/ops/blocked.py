"""Blocked (cache-line) bloom-filter kernels — the throughput layout.

Why this exists: the flat spec (tpubloom.ops.hashing) scatters each key's
k bits uniformly over the whole m-bit array — k random 4-byte HBM
accesses per key. TPU HBM serves random traffic at sector granularity
(~512 B), so the flat hot path is latency-bound at roughly
``k × (random access rate)``. The blocked layout (Putze, Sanders &
Singler 2007, "Cache-, Hash- and Space-Efficient Bloom Filters")
confines all k bits of a key to ONE ``block_bits``-sized block:

* one contiguous 64–512 B row gather per query (vs k scattered reads),
* one row read-modify-write per insert (vs k scattered RMWs),

i.e. ~k× less random HBM traffic, which measured ~10× faster end-to-end
on v5e at m=2^32, k=7. The price is a slightly higher FPR at high fill
(block loads are Poisson-skewed); at the north-star operating point
(fill ≈ 6%) the excess is negligible. See BloomFilter docstrings for the
user-facing guidance.

THE BLOCKED POSITION SPEC (canonical; CPU oracle + tests mirror it)
-------------------------------------------------------------------
Given the four base hashes of the flat spec (h_a, h_b, g_a, g_b — see
tpubloom.ops.hashing), ``n_blocks = m / block_bits`` (both powers of 2):

  blk = h_a mod n_blocks                          # owning block

and, with ``b`` the in-block position count (= block_bits here; the
blocked COUNTING layout reuses this function with b = counters per
block), TWO in-block variants selected by ``config.block_hash``:

``"chunk"`` (default where it fits — see config.FilterConfig):

  pool    = h_b | g_a<<32 | g_b<<64                # 96-bit hash pool
  bit_i   = (pool >> (i·log2(b))) mod b,  i = 0..k-1

i.e. each position reads a disjoint log2(b)-bit slice of the pool —
positions are i.i.d. uniform. Requires k·log2(b) <= 96.

``"ap"`` (legacy):

  p_i     = (g_a + i·(g_b | 1)) mod 2^32,  i = 0..k-1
  bit_i   = p_i mod b

The AP variant's position SET is determined by just
(g_a mod b, g_b mod b) — a 2-parameter family of arithmetic
progressions. Two same-block keys colliding in those ~2·log2(b) bits
share every position, which floors the filter's FPR at ~4·load/b²
regardless of fill (measured: 1.6e-4 at the north-star shape where
theory says 1e-6 — see params.blocked_fpr and tests/test_fpr_model.py).
"chunk" removes that floor; "ap" remains supported to restore
checkpoints written before the field existed.

Bit ``bit_i`` of a block is bit ``bit_i mod 32`` (LSB-first) of word
``bit_i div 32`` in the block's ``uint32[block_bits/32]`` row. Blocked
arrays are therefore NOT bit-compatible with flat arrays; the layout is
part of the filter's identity (config.block_bits, config.block_hash).

AP in-block positions cannot collide within a key when b is a power of
two (odd stride), chunk positions can (i.i.d.) — standard bloom
behavior; the FPR model accounts for both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tpubloom.ops import hashing
from tpubloom.ops.bitops import segmented_scan_last


def _u32(x) -> jnp.ndarray:
    return jnp.asarray(x, jnp.uint32)


def block_positions(
    keys: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    n_blocks: int,
    block_bits: int,
    k: int,
    seed: int,
    block_hash: str = "ap",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked-spec coordinates of each key (module docstring has the spec).

    Returns ``(blk, bit)``: ``blk`` int32[...], owning block per key;
    ``bit`` uint32[..., k], in-block bit positions. ``block_hash`` selects
    the in-block variant ("chunk" / "ap"); callers with a FilterConfig
    must pass ``config.block_hash`` — it is part of the filter identity.
    Traced under the hash stage's named scope (``hashing.HASH_SCOPE``).
    """
    with jax.named_scope(hashing.HASH_SCOPE):
        return _block_positions(
            keys, lengths, n_blocks=n_blocks, block_bits=block_bits, k=k,
            seed=seed, block_hash=block_hash,
        )


def _block_positions(keys, lengths, *, n_blocks, block_bits, k, seed,
                     block_hash):
    h_a = hashing.murmur3_32(keys, lengths, seed)
    g_a = hashing.fnv1a_32(keys, lengths)
    g_b = hashing.murmur3_32(keys, lengths, seed ^ hashing.SEED_XOR_GB)
    blk = (h_a & _u32(n_blocks - 1)).astype(jnp.int32)
    mask = _u32(block_bits - 1)
    bits = []
    if block_hash == "chunk":
        nb = (block_bits - 1).bit_length()
        if k * nb > 96:
            raise ValueError(
                f"chunk in-block hash needs k*log2(block_bits) <= 96 "
                f"(k={k}, {nb} bits/position)"
            )
        h_b = hashing.murmur3_32(keys, lengths, seed ^ hashing.SEED_XOR_HB)
        pool = (h_b, g_a, g_b)
        for i in range(k):
            sh = i * nb
            w, off = sh >> 5, sh & 31
            v = pool[w] >> _u32(off)
            if off + nb > 32:
                v = v | (pool[w + 1] << _u32(32 - off))
            bits.append(v & mask)
    elif block_hash == "ap":
        stride = g_b | _u32(1)
        p = g_a
        for i in range(k):
            if i > 0:
                p = p + stride  # u32 wrap == mod 2^32
            bits.append(p & mask)
    else:
        raise ValueError(f"block_hash must be 'chunk' or 'ap', got {block_hash!r}")
    return blk, jnp.stack(bits, axis=-1)


def build_masks(bit: jnp.ndarray, words_per_block: int) -> jnp.ndarray:
    """OR the k in-block positions into per-key row masks.

    ``bit``: uint32[B, k] in-block positions -> uint32[B, W] row masks,
    W = words_per_block. Dense VPU work: B×k×W compares, no gathers.
    """
    word = (bit >> _u32(5)).astype(jnp.int32)  # [B, k] in [0, W)
    one = _u32(1) << (bit & _u32(31))  # [B, k]
    iota = lax.broadcasted_iota(jnp.int32, (1, words_per_block), 1)  # [1, W]
    k = bit.shape[-1]
    mask = jnp.zeros(bit.shape[:-1] + (words_per_block,), jnp.uint32)
    for i in range(k):  # k is static and small; OR-accumulate one-hot words
        mask = mask | jnp.where(
            word[..., i, None] == iota, one[..., i, None], _u32(0)
        )
    return mask  # [B, W]


def _replicate_masks_128(masks: jnp.ndarray) -> jnp.ndarray:
    """[B, W] u32 -> [B, 128] with the mask repeated in every lane group,
    via 4 exact byte-quarter matmuls against a constant [W, 128] 0/1
    weight (byte values <= 255 are bf16-exact; f32 accumulation).

    Why a matmul: a [B, W] array is ALREADY 128-lane padded in TPU
    layout, so every lane-space alternative is a real cross-row
    relayout at B=4M — ``concatenate([masks]*J, axis=1)`` costs ~47 ms
    (benchmarks/out/query_probe_r5.json q3) and static lane slices of a
    [B, 128] operand cost ~20 ms EACH (benchmarks/out/query_fix_r5.json
    variant A, ~126 ms over the fold for J=8 slices). The MXU
    replicates across lanes for free: measured 106 ms vs 232 ms (slices)
    vs 114 ms (concat) for the full query step at B=4M."""
    B, w = masks.shape
    iw = lax.broadcasted_iota(jnp.int32, (w, 128), 0)
    il = lax.broadcasted_iota(jnp.int32, (w, 128), 1)
    sel = (il % w == iw).astype(jnp.bfloat16)  # [W, 128] 0/1
    out = jnp.zeros((B, 128), jnp.uint32)
    for b in range(4):
        q = ((masks >> _u32(8 * b)) & _u32(0xFF)).astype(jnp.bfloat16)
        rep = lax.dot_general(
            q, sel, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        out = out | (rep.astype(jnp.uint32) << _u32(8 * b))
    return out


def fat_fold_masks(
    blk: jnp.ndarray, masks: jnp.ndarray, J: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Translate (block id, [B, W] mask) pairs to the fat [NB/J, 128]
    view: returns ``(fat_row[B], masks128[B, 128])`` with each mask
    placed at lane group ``blk % J``. Lets the scatter/gather fallbacks
    operate on fat storage DIRECTLY — a [NB, W] <-> fat reshape is a
    real ~26 ms copy at m=2^32 on TPU (benchmarks/RESULTS_r3.md §2),
    while this fold is 4 constant-weight matmuls + one select (see
    :func:`_replicate_masks_128` for why NOT lane concat or slices).
    ``blocked_insert``/``blocked_query`` accept the folded pair
    unchanged (they are generic over row width; distinct blocks sharing
    a fat row merge by OR at disjoint lanes).
    """
    B, w = masks.shape
    lane = lax.broadcasted_iota(jnp.int32, (B, 128), 1)
    sel = (lane // w) == (blk % J).astype(jnp.int32)[:, None]
    return (blk // J).astype(jnp.int32), jnp.where(
        sel, _replicate_masks_128(masks), _u32(0)
    )


def blocked_insert(
    blocks: jnp.ndarray, blk: jnp.ndarray, masks: jnp.ndarray, valid: jnp.ndarray
) -> jnp.ndarray:
    """OR each key's mask row into its block. Duplicate blocks within the
    batch are merged by a sort + segmented row-OR so the final row scatter
    has unique indices (same recipe as bitops.scatter_or, at row granularity
    — 1 sort of B elements instead of B·k).

    ``valid == False`` entries (batch padding) are redirected out of bounds
    and dropped by the scatter.
    """
    n_blocks = blocks.shape[0]
    b = jnp.where(valid, blk, n_blocks).astype(jnp.int32)
    order = jnp.argsort(b)
    bs = b[order]
    rows, is_last = segmented_scan_last(bs, masks[order], jnp.bitwise_or)
    target = jnp.where(is_last & (bs < n_blocks), bs, n_blocks)
    current = blocks[jnp.minimum(bs, n_blocks - 1)]
    merged = current | rows
    return blocks.at[target].set(merged, mode="drop", unique_indices=True)


def fat_blocked_query(
    blocks_fat: jnp.ndarray, blk: jnp.ndarray, masks: jnp.ndarray
) -> jnp.ndarray:
    """Membership against the fat [NB/J, 128] view: gather each key's fat
    row, fold the mask to the owning lane group with the matmul
    replication (:func:`_replicate_masks_128`), one full-width compare.

    Every lane-space alternative measured slower at B=4M
    (benchmarks/out/query_fix_r5.json): J static-slice compares 232 ms
    (each slice is a hidden cross-lane relayout), lane-concat fold
    114 ms, this path 106 ms against a 70 ms gather-only floor.
    take_along_axis / multi-index lax.gather scalarize outright
    (measured r4: 9x and 54x collapses)."""
    w = masks.shape[-1]
    J = 128 // w
    frow, m128 = fat_fold_masks(blk, masks, J)
    rows128 = blocks_fat[frow]  # [B, 128] row gather
    return jnp.all((rows128 & m128) == m128, axis=-1)


def blocked_query(
    blocks: jnp.ndarray, blk: jnp.ndarray, masks: jnp.ndarray
) -> jnp.ndarray:
    """Membership: one row gather per key + all-mask-bits-present test.

    Padded entries carry the empty-key verdict (length is clamped to 0
    upstream, so their masks are the hash of ``b""``, not zeros) — callers
    must trim the batch (include_batch) or mask the result (sharded
    ``owned``); the values at padded positions are meaningless.
    """
    rows = blocks[blk]
    return jnp.all((rows & masks) == masks, axis=-1)
