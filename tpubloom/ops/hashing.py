"""Device-side hash family: MurmurHash3_x86_32 + FNV-1a over fixed-shape keys.

This module is the framework's **bit-exactness contract**. The CPU oracle
(:mod:`tpubloom.cpu_ref`), the C++ native library (``tpubloom/native``) and
these jnp kernels must all produce identical bits; tests enforce it against
published test vectors and with hypothesis-generated keys.

Parity: the reference's hot path is "k× MurmurHash3/FNV-1a hashing followed
by SETBIT/GETBIT against the m-bit array" (BASELINE.json north_star;
SURVEY.md §2.1 "Hashing engine" — double hashing h_i = h1 + i·h2 mod m is
the standard trick to derive k positions from 2 base hashes).

THE POSITION SPEC (canonical, shared by every implementation)
-------------------------------------------------------------
Keys are byte strings of length ``len <= key_len``, zero-padded on device to
``uint8[B, key_len]`` with true lengths in ``int32[B]``. All hashing is over
the *true* bytes (padding never changes a hash — murmur3's tail construction
and fnv1a's byte loop are masked by length).

Base hashes (u32 each)::

  h_a = murmur3_32(key, seed)
  h_b = murmur3_32(key, seed XOR 0x9E3779B9)      # golden ratio
  g_a = fnv1a_32(key)
  g_b = murmur3_32(key, seed XOR 0x85EBCA6B)      # murmur fmix constant

Positions, power-of-two m (m = 2^logm, logm <= 36)::

  H1 = h_b·2^32 + h_a
  H2 = (g_b·2^32 + g_a) | 1                        # odd stride
  pos_i = (H1 + i·H2 mod 2^64) mod m,  i = 0..k-1

Positions, non-power-of-two m (m < 2^31)::

  pos_i = ((h_a + i·(g_a | 1)) mod 2^32) mod m

The 64-bit arithmetic is carried out in u32 (hi, lo) pairs on device — TPUs
have no u64 — via k-step iterative addition with carry, which is exactly
``(H1 + i·H2) mod 2^64``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# MurmurHash3_x86_32 constants (public domain algorithm by Austin Appleby).
_C1 = 0xCC9E2D51
_C2 = 0x1B873593
_FMIX1 = 0x85EBCA6B
_FMIX2 = 0xC2B2AE35

# FNV-1a 32-bit constants.
_FNV_OFFSET = 0x811C9DC5
_FNV_PRIME = 0x01000193

# Seed derivation constants (part of the position spec above).
SEED_XOR_HB = 0x9E3779B9
SEED_XOR_GB = 0x85EBCA6B
# Shard-routing hash seed (sharded filter array, BASELINE config 5):
# shard(key) = murmur3_32(key, seed XOR SEED_XOR_ROUTE) mod n_shards.
# Independent of the position hashes so routing doesn't correlate with
# within-shard positions.
SEED_XOR_ROUTE = 0x517CC1B7


#: ``jax.named_scope`` of the hash stage of every filter program (the
#: sweep stages are ``tpubloom/sort``, ``/sweep`` and ``/unsort``, in
#: :mod:`tpubloom.ops.sweep`): the device ops it emits carry it in their
#: HLO ``op_name`` metadata, so a profile can group them by stage.
HASH_SCOPE = "tpubloom/hash"


def _u32(x) -> jnp.ndarray:
    return jnp.asarray(x, jnp.uint32)


def _rotl32(x: jnp.ndarray, r: int) -> jnp.ndarray:
    r = r % 32
    return (x << _u32(r)) | (x >> _u32(32 - r))


def murmur3_32(keys: jnp.ndarray, lengths: jnp.ndarray, seed) -> jnp.ndarray:
    """MurmurHash3_x86_32 of each key.

    Args:
      keys: ``uint8[..., L]`` zero-padded key bytes, L a multiple of 4.
        Bytes at positions >= length MUST be zero (``pack_keys`` guarantees
        this); they flow into the tail word construction, where zeros are
        exactly what the reference algorithm's partial tail load produces.
      lengths: ``int32[...]`` true byte lengths, 0 <= length <= L.
      seed: u32 seed (python int or u32 array broadcastable to lengths).

    Returns:
      ``uint32[...]`` hashes, bit-exact with the canonical C implementation.
    """
    L = keys.shape[-1]
    if L % 4 != 0:
        raise ValueError(f"key buffer length must be a multiple of 4, got {L}")
    # Little-endian 32-bit blocks: block[i] = bytes[4i] | bytes[4i+1]<<8 | ...
    # — exactly what a little-endian bitcast of 4 consecutive bytes gives
    # (XLA bitcast_convert_type is LE on every supported backend; the
    # strided-shift formulation is equivalent but costs 4 strided u8
    # relayouts per block on TPU). The astype is a no-op for the uint8
    # arrays every internal caller passes; it keeps byte values in wider
    # dtypes bit-exact rather than silently mis-bitcasting them.
    blocks = lax.bitcast_convert_type(
        keys.astype(jnp.uint8).reshape(keys.shape[:-1] + (L // 4, 4)),
        jnp.uint32,
    )
    lengths = lengths.astype(jnp.int32)
    h = jnp.broadcast_to(_u32(seed), lengths.shape)
    c1, c2 = _u32(_C1), _u32(_C2)
    for i in range(L // 4):
        blk = blocks[..., i]
        kk = blk * c1
        kk = _rotl32(kk, 15)
        kk = kk * c2
        rem = lengths - 4 * i  # bytes of the key at/after this block
        # Full block: mix + rotate + scramble. Tail (1-3 bytes): mix only.
        h_full = _rotl32(h ^ kk, 13) * _u32(5) + _u32(0xE6546B64)
        h_tail = h ^ kk
        h = jnp.where(rem >= 4, h_full, jnp.where(rem > 0, h_tail, h))
    # Finalization.
    h = h ^ lengths.astype(jnp.uint32)
    h = h ^ (h >> _u32(16))
    h = h * _u32(_FMIX1)
    h = h ^ (h >> _u32(13))
    h = h * _u32(_FMIX2)
    h = h ^ (h >> _u32(16))
    return h


def fnv1a_32(keys: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """FNV-1a 32-bit of each key (same shape contract as :func:`murmur3_32`).

    The byte loop is unrolled over the static buffer length and masked by the
    true length, so padding bytes never enter the hash.
    """
    L = keys.shape[-1]
    lengths = lengths.astype(jnp.int32)
    h = jnp.broadcast_to(_u32(_FNV_OFFSET), lengths.shape)
    prime = _u32(_FNV_PRIME)
    if L % 4 == 0:
        # extract bytes from bitcast u32 words (4 lanes instead of 16
        # strided u8 lanes — cheaper layout on TPU)
        words = lax.bitcast_convert_type(
            keys.astype(jnp.uint8).reshape(keys.shape[:-1] + (L // 4, 4)),
            jnp.uint32,
        )
        byte = lambda j: (words[..., j >> 2] >> _u32(8 * (j & 3))) & _u32(0xFF)
    else:
        kb = keys.astype(jnp.uint32)
        byte = lambda j: kb[..., j]
    for j in range(L):
        h_next = (h ^ byte(j)) * prime
        h = jnp.where(j < lengths, h_next, h)
    return h


def base_hashes(
    keys: jnp.ndarray, lengths: jnp.ndarray, seed: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The four u32 base hashes ``(h_a, h_b, g_a, g_b)`` of the spec."""
    h_a = murmur3_32(keys, lengths, seed)
    h_b = murmur3_32(keys, lengths, seed ^ SEED_XOR_HB)
    g_a = fnv1a_32(keys, lengths)
    g_b = murmur3_32(keys, lengths, seed ^ SEED_XOR_GB)
    return h_a, h_b, g_a, g_b


def positions(
    keys: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    m: int,
    k: int,
    seed: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The k filter positions of each key, as u32 (hi, lo) pairs.

    Returns:
      ``(pos_hi, pos_lo)``, each ``uint32[..., k]``, with
      position = pos_hi·2^32 + pos_lo, already reduced mod m.
      For m <= 2^32, pos_hi is all zeros.
    """
    if (m & (m - 1)) == 0:
        with jax.named_scope(HASH_SCOPE):
            return _positions_pow2(keys, lengths, m=m, k=k, seed=seed)
    if m >= (1 << 31):
        raise ValueError("non-power-of-two m must be < 2^31")
    with jax.named_scope(HASH_SCOPE):
        return _positions_mod(keys, lengths, m=m, k=k, seed=seed)


def _positions_pow2(keys, lengths, *, m: int, k: int, seed: int):
    logm = m.bit_length() - 1
    if logm > 36:
        # split_word_bit packs word = pos >> 5 into int32: logm <= 36 keeps
        # word < 2^31. Larger filters must shard (config 5 path).
        raise ValueError(f"m up to 2^36 supported, got 2^{logm}")
    h_a, h_b, g_a, g_b = base_hashes(keys, lengths, seed)
    g_a = g_a | _u32(1)  # odd 64-bit stride
    lo, hi = h_a, h_b
    lo_mask = _u32(0xFFFFFFFF if logm >= 32 else (1 << logm) - 1)
    hi_mask = _u32((1 << (logm - 32)) - 1 if logm > 32 else 0)
    out_hi, out_lo = [], []
    for i in range(k):
        if i > 0:
            # (hi, lo) += (g_b, g_a) mod 2^64 — carry via unsigned wrap test.
            lo_next = lo + g_a
            carry = (lo_next < lo).astype(jnp.uint32)
            hi = hi + g_b + carry
            lo = lo_next
        out_lo.append(lo & lo_mask)
        out_hi.append(hi & hi_mask)
    return jnp.stack(out_hi, axis=-1), jnp.stack(out_lo, axis=-1)


def _positions_mod(keys, lengths, *, m: int, k: int, seed: int):
    h_a = murmur3_32(keys, lengths, seed)
    g_a = fnv1a_32(keys, lengths) | _u32(1)
    out = []
    pos = h_a
    for i in range(k):
        if i > 0:
            pos = pos + g_a  # u32 wrap == mod 2^32
        out.append(pos % _u32(m))
    lo = jnp.stack(out, axis=-1)
    return jnp.zeros_like(lo), lo


def route_shards(
    keys: jnp.ndarray, lengths: jnp.ndarray, *, n_shards: int, seed: int
) -> jnp.ndarray:
    """Owning shard of each key: ``uint32[...]`` in [0, n_shards)."""
    h = murmur3_32(keys, lengths, seed ^ SEED_XOR_ROUTE)
    return h % _u32(n_shards)


def split_word_bit(
    pos_hi: jnp.ndarray, pos_lo: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Packed-u32 bit-array coordinates of positions.

    word = pos >> 5 (int32 — valid for m <= 2^36), bit = pos & 31.
    Bit b of word w is ``(1 << b)`` — LSB-first within the word. The
    Redis-bitmap byte order conversion lives in ``tpubloom.utils.packing``.
    """
    word = ((pos_lo >> _u32(5)) | (pos_hi << _u32(27))).astype(jnp.int32)
    bit = pos_lo & _u32(31)
    return word, bit


def split_counter(
    pos_hi: jnp.ndarray, pos_lo: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Packed 4-bit-counter coordinates: word = pos >> 3, nibble = pos & 7."""
    word = ((pos_lo >> _u32(3)) | (pos_hi << _u32(29))).astype(jnp.int32)
    nib = pos_lo & _u32(7)
    return word, nib
