"""BloomFilter / CountingBloomFilter — the framework's front-end classes.

Parity: mirrors the reference's public API — ``#insert`` / ``#include?`` /
``#clear`` on ``Redis::Bloomfilter`` (SURVEY.md §1 L1; BASELINE.json: "keeps
#insert / #include?") — plus the batch forms the north star adds
(``insert_batch`` / ``include_batch``), the counting variant (config 4), and
checkpoint import/export in the reference's Redis-string-bitmap format.

TPU-first mechanics:

* the bit array is a device-resident packed ``uint32`` array; insert/query
  are jit-compiled once per padded batch shape;
* the insert jit **donates** the bit-array buffer, so updates are in-place in
  HBM — no 512 MiB copy per batch at m=2^32;
* host batches are padded to the next power of two (min 64) to bound the
  jit cache; padded entries carry ``length = -1`` and are dropped in-kernel;
* ``insert_arrays`` / ``include_arrays`` accept pre-packed device arrays for
  zero-host-overhead streaming (bench path, gRPC server path).
"""

from __future__ import annotations

import logging
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tpubloom.config import FilterConfig
from tpubloom.obs import context as obs
from tpubloom.obs import counters as obs_counters
from tpubloom.ops import bitops, blocked, counting, hashing
from tpubloom.utils.packing import (
    pack_keys,
    redis_bitmap_to_words,
    words_to_redis_bitmap,
)

log = logging.getLogger(__name__)


def _pad_to_bucket(n: int, minimum: int = 64) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


# -- pure kernels (shared with sharded/pipeline/graft paths) -----------------


def make_insert_fn(config: FilterConfig):
    """Pure ``(bits, keys_u8[B,L], lengths[B]) -> bits`` insert step.

    ``lengths < 0`` marks padding. This is the function the single-chip
    benchmark jits with buffer donation and the sharded filter wraps in
    ``shard_map``.
    """
    m, k, seed = config.m, config.k, config.seed

    def insert(bits, keys_u8, lengths):
        valid = lengths >= 0
        ph, pl = hashing.positions(
            keys_u8, jnp.maximum(lengths, 0), m=m, k=k, seed=seed
        )
        word, bit = hashing.split_word_bit(ph, pl)
        valid_k = jnp.broadcast_to(valid[..., None], word.shape)
        return bitops.scatter_or(bits, word.ravel(), bit.ravel(), valid_k.ravel())

    return insert


def make_query_fn(config: FilterConfig):
    """Pure ``(bits, keys_u8, lengths) -> bool[B]`` membership step."""
    m, k, seed = config.m, config.k, config.seed

    def query(bits, keys_u8, lengths):
        ph, pl = hashing.positions(
            keys_u8, jnp.maximum(lengths, 0), m=m, k=k, seed=seed
        )
        word, bit = hashing.split_word_bit(ph, pl)
        return bitops.query_membership(bits, word, bit)

    return query


def make_counter_fn(config: FilterConfig, *, increment: bool):
    m, k, seed = config.m, config.k, config.seed

    def update(words, keys_u8, lengths):
        valid = lengths >= 0
        ph, pl = hashing.positions(
            keys_u8, jnp.maximum(lengths, 0), m=m, k=k, seed=seed
        )
        del ph  # counting m < 2^31 => positions fit the low word
        pos = pl.astype(jnp.int32)
        valid_k = jnp.broadcast_to(valid[..., None], pos.shape)
        return counting.counter_update(
            words, pos.ravel(), valid_k.ravel(), increment=increment
        )

    return update


def make_counting_query_fn(config: FilterConfig):
    m, k, seed = config.m, config.k, config.seed

    def query(words, keys_u8, lengths):
        _, pl = hashing.positions(
            keys_u8, jnp.maximum(lengths, 0), m=m, k=k, seed=seed
        )
        return counting.counting_membership(words, pl.astype(jnp.int32))

    return query


def blocked_storage_fat(config: FilterConfig) -> bool:
    """Whether the persistent blocked storage uses the fat [NB/J, 128]
    view (the SAME row-major bytes as [NB, W]): XLA's tiled HBM layouts
    make narrow-lane arrays both slow to DMA and expensive to reshape,
    so every filter that can holds its device array fat. Applies to both
    plain-blocked and blocked-counting layouts (the fat counting sweep
    ships since round 4)."""
    w = config.words_per_block
    return 128 % w == 0 and config.n_blocks % (128 // w) == 0


def blocked_device_shape(config: FilterConfig) -> tuple[int, int]:
    """Device-array shape for blocked storage (plain or counting): the
    fat [NB*W/128, 128] view when :func:`blocked_storage_fat` holds,
    else the logical [NB, W]. The ONE place the fat geometry is spelled
    out for single-chip filters."""
    nb, w = config.n_blocks, config.words_per_block
    if blocked_storage_fat(config):
        return (nb * w // 128, 128)
    return (nb, w)


def _log_path(op: str, path: str, config: FilterConfig, batch: int) -> str:
    """Log the kernel path a blocked op resolved to. Called while jit
    traces, so it prints once per (filter, batch shape) — on the first
    launch of that shape; ``chip_smoke.py`` reads these lines."""
    log.info(
        "blocked %s path=%s batch=%d n_blocks=%d", op, path, batch,
        config.n_blocks,
    )
    return path


def make_blocked_insert_fn(config: FilterConfig, *, storage_fat: bool = False):
    """Pure ``(blocks[NB,W], keys_u8[B,L], lengths[B]) -> blocks`` insert for
    the blocked layout (ops.blocked spec).

    ``config.insert_path`` selects the implementation: the Pallas
    partition-sweep kernel (``tpubloom.ops.sweep`` — the TPU fast path)
    or the pure-XLA sorted scatter. Both produce bit-identical arrays;
    "auto" decides per (backend, batch shape) at trace time.
    ``storage_fat``: blocks are the fat [NB/J, 128] view in and out.
    """
    nb, bb, w = config.n_blocks, config.block_bits, config.words_per_block
    k, seed, bh = config.k, config.seed, config.block_hash

    def insert(blocks, keys_u8, lengths):
        from tpubloom.ops import sweep

        B = keys_u8.shape[0]
        path = sweep.resolve_insert_path(config, B)
        if _log_path("insert", path, config, B) == "sweep":
            return sweep.make_sweep_insert_fn(config, storage_fat=storage_fat)(
                blocks, keys_u8, lengths
            )
        valid = lengths >= 0
        blk, bit = blocked.block_positions(
            keys_u8, jnp.maximum(lengths, 0),
            n_blocks=nb, block_bits=bb, k=k, seed=seed, block_hash=bh,
        )
        masks = blocked.build_masks(bit, w)
        if storage_fat:
            # scatter straight into the fat view (a [NB, W] <-> fat
            # reshape is a real copy on TPU; the lane fold is O(B))
            frow, m128 = blocked.fat_fold_masks(blk, masks, 128 // w)
            return blocked.blocked_insert(blocks, frow, m128, valid)
        return blocked.blocked_insert(blocks, blk, masks, valid)

    return insert


def make_blocked_counter_fn(
    config: FilterConfig, *, increment: bool, storage_fat: bool = False
):
    """Pure ``(blocks[NB,W], keys_u8, lengths) -> blocks`` update for the
    BLOCKED counting layout: all k 4-bit counters of a key live in one
    block (block_bits bits = block_bits/4 counters), so the sweep path
    touches one row per key instead of k scattered words.

    Position spec: ``blk`` as in ops.blocked; counter ``c_i = p_i mod
    counters_per_block``. The storage is bit-identical to the flat
    counting layout at positions ``blk * counters_per_block + c`` —
    which is exactly what the non-sweep fallback (and the CPU oracle)
    computes via ops.counting.counter_update on the raveled array.
    ``storage_fat``: blocks are the fat [NB/J, 128] view in and out
    (same raveled bytes, so the flat fallback is layout-agnostic).
    """
    nb, cpb, w = config.n_blocks, config.counters_per_block, config.words_per_block
    k, seed, bh = config.k, config.seed, config.block_hash

    def update(blocks, keys_u8, lengths):
        from tpubloom.ops import sweep

        if sweep.resolve_insert_path(config, keys_u8.shape[0]) == "sweep":
            if k > 15:
                # per-key multiplicity must fit the 4-bit stream nibbles
                if config.insert_path == "sweep":
                    raise ValueError(
                        "counting sweep supports k <= 15 — use "
                        "insert_path='scatter' (auto falls back silently)"
                    )
            else:
                return sweep.make_sweep_counter_fn(
                    config, increment=increment, storage_fat=storage_fat
                )(blocks, keys_u8, lengths)
        valid = lengths >= 0
        blk, cpos = blocked.block_positions(
            keys_u8, jnp.maximum(lengths, 0),
            n_blocks=nb, block_bits=cpb, k=k, seed=seed, block_hash=bh,
        )
        gpos = (blk[..., None] * cpb + cpos.astype(jnp.int32)).astype(jnp.int32)
        valid_k = jnp.broadcast_to(valid[..., None], gpos.shape)
        flat = counting.counter_update(
            blocks.reshape(-1), gpos.ravel(), valid_k.ravel(), increment=increment
        )
        return flat.reshape(blocks.shape)

    return update


def make_blocked_counting_query_fn(
    config: FilterConfig, *, storage_fat: bool = False
):
    """Pure ``(blocks, keys_u8, lengths) -> bool[B]`` blocked-counting
    membership: one row gather per key + all-counters-nonzero test.
    With ``storage_fat`` the gather reads fat [NB/J, 128] rows directly
    (row = blk // J, lane group blk % J), like the plain blocked query."""
    nb, cpb, w = config.n_blocks, config.counters_per_block, config.words_per_block
    k, seed, bh = config.k, config.seed, config.block_hash

    def query(blocks, keys_u8, lengths):
        blk, cpos = blocked.block_positions(
            keys_u8, jnp.maximum(lengths, 0),
            n_blocks=nb, block_bits=cpb, k=k, seed=seed, block_hash=bh,
        )
        if not storage_fat:
            return counting.blocked_counting_membership(blocks, blk, cpos)
        return counting.fat_blocked_counting_membership(blocks, blk, cpos, w)

    return query


def make_blocked_test_insert_fn(config: FilterConfig, *, storage_fat: bool = False):
    """Pure ``(blocks, keys_u8, lengths) -> (blocks, present[B])``
    test-and-insert for the blocked layout: ``present[i]`` is key i's
    membership BEFORE this batch (within-batch duplicates all report the
    pre-batch state; padded entries report False).

    Parity: the reference's Lua add script returns prior membership from
    the same server-side pass that sets the bits (SURVEY.md §2.1 ":lua"
    driver row); this is that fused hot path. On TPU the sweep kernel
    answers membership from the partition tile it is already updating —
    measurably faster than separate query + insert steps.
    """
    nb, bb, w = config.n_blocks, config.block_bits, config.words_per_block
    k, seed, bh = config.k, config.seed, config.block_hash

    def test_insert(blocks, keys_u8, lengths):
        from tpubloom.ops import sweep

        B = keys_u8.shape[0]
        path = sweep.resolve_insert_path(config, B, presence=True)
        if _log_path("test_insert", path, config, B) == "sweep":
            return sweep.make_sweep_insert_fn(
                config, with_presence=True, storage_fat=storage_fat
            )(blocks, keys_u8, lengths)
        # scatter path: hash once, reuse positions for both the
        # membership test and the insert
        valid = lengths >= 0
        blk, bit = blocked.block_positions(
            keys_u8, jnp.maximum(lengths, 0),
            n_blocks=nb, block_bits=bb, k=k, seed=seed, block_hash=bh,
        )
        masks = blocked.build_masks(bit, w)
        if storage_fat:
            blk, masks = blocked.fat_fold_masks(blk, masks, 128 // w)
        present = blocked.blocked_query(blocks, blk, masks) & valid
        out = blocked.blocked_insert(blocks, blk, masks, valid)
        return out, present

    return test_insert


def make_blocked_query_fn(config: FilterConfig, *, storage_fat: bool = False):
    """Pure ``(blocks, keys_u8, lengths) -> bool[B]`` blocked membership.

    ``config.query_path`` selects the implementation (ISSUE 12): the
    read-only Pallas query sweep (``tpubloom.ops.sweep`` — sorted window
    fetch + nibble-extraction presence test, no write-back, no donated
    chain) or the row-gather XLA path. Both answer bit-identical
    verdicts; "auto" decides per (backend, batch shape) at trace time
    through :func:`tpubloom.ops.sweep.resolve_query_path`.

    With ``storage_fat`` the gather reads fat [NB/J, 128] rows directly
    (row = blk // J, lane group blk % J) — no reshape of the array."""
    nb, bb, w = config.n_blocks, config.block_bits, config.words_per_block
    k, seed, bh = config.k, config.seed, config.block_hash
    J = 128 // w if w and 128 % w == 0 else 1

    def query(blocks, keys_u8, lengths):
        from tpubloom.ops import sweep

        # effective (not just resolved) path: a forced "sweep" on a
        # shape the kernel cannot take demotes to the gather here —
        # served filters see arbitrary batch sizes
        B = keys_u8.shape[0]
        path = sweep.effective_query_path(config, B)
        if _log_path("query", path, config, B) == "sweep":
            return sweep.make_sweep_query_fn(config, storage_fat=storage_fat)(
                blocks, keys_u8, lengths
            )
        blk, bit = blocked.block_positions(
            keys_u8, jnp.maximum(lengths, 0),
            n_blocks=nb, block_bits=bb, k=k, seed=seed, block_hash=bh,
        )
        masks = blocked.build_masks(bit, w)
        if not storage_fat:
            return blocked.blocked_query(blocks, blk, masks)
        return blocked.fat_blocked_query(blocks, blk, masks)

    return query


# -- front-end classes -------------------------------------------------------


class _FilterBase:
    """Shared packing / padding / batch plumbing.

    Subclasses provide ``self._insert`` / ``self._query`` (jitted pure
    kernels over ``self.words``) and inherit the whole batch + scalar API;
    only construction, stats, and persistence differ per variant.
    """

    def __init__(self, config: FilterConfig, n_storage_words: int):
        self.config = config
        self.n_inserted = 0
        self.n_queried = 0
        self.words = jnp.zeros((n_storage_words,), jnp.uint32)

    def _pack_padded(self, keys: Sequence[bytes | str]):
        # obs.phase spans are no-ops outside an active request context
        # (the gRPC server / bench open one) — see tpubloom.obs.context
        with obs.phase("host_prep"):
            keys_u8, lengths = pack_keys(
                keys, self.config.key_len, key_policy=self.config.key_policy
            )
            B = len(keys)
            Bp = _pad_to_bucket(B)
            if Bp != B:
                keys_u8 = np.pad(keys_u8, ((0, Bp - B), (0, 0)))
                lengths = np.pad(lengths, (0, Bp - B), constant_values=-1)
        return keys_u8, lengths, B

    def _stage_batch(self, keys_u8, lengths):
        """H2D staging under its own phase span, so the breakdown
        separates transfer-bound from kernel-bound time server-side."""
        with obs.phase("h2d"):
            return jnp.asarray(keys_u8), jnp.asarray(lengths)

    def _prep_packed(self, rows: np.ndarray):
        """Host prep for FIXED-WIDTH pre-packed keys (the ``fixed`` wire
        encoding, ISSUE 10): ``rows`` is ``uint8[B, W]`` — every key
        exactly W bytes. Skips the per-key packing loop entirely; pads
        columns to ``key_len`` and rows to the jit bucket (both
        vectorized; zero copies when W == key_len and B is already a
        bucket size)."""
        with obs.phase("host_prep"):
            B, W = rows.shape
            key_len = self.config.key_len
            if W > key_len:
                raise ValueError(
                    f"fixed-width keys are {W} bytes > key_len={key_len}; "
                    "ship them msgpack-encoded (key_policy applies there)"
                )
            if W < key_len:
                rows = np.pad(rows, ((0, 0), (0, key_len - W)))
            lengths = np.full((B,), W, dtype=np.int32)
            Bp = _pad_to_bucket(B)
            if Bp != B:
                rows = np.pad(rows, ((0, Bp - B), (0, 0)))
                lengths = np.pad(lengths, (0, Bp - B), constant_values=-1)
        return rows, lengths, B

    # staged pipeline API (ISSUE 10): host_prep + H2D split from the
    # kernel launch, so a batching caller (the server's ingestion
    # coalescer, bench drivers) can stage batch N+1 while batch N's
    # kernel is still in flight, then fence N via the returned handle —
    # double-buffering the host feed against the device.

    def stage_batch(self, keys=None, *, rows=None):
        """Host prep + H2D only — returns an opaque staged batch for
        :meth:`launch_insert` / :meth:`launch_query`. Exactly one of
        ``keys`` (a key sequence) or ``rows`` (fixed-width ``uint8[B,
        W]``) must be given."""
        if rows is not None:
            keys_u8, lengths, B = self._prep_packed(np.asarray(rows, np.uint8))
        else:
            keys_u8, lengths, B = self._pack_padded(keys)
        d_keys, d_lengths = self._stage_batch(keys_u8, lengths)
        return d_keys, d_lengths, B

    def launch_insert(self, staged):
        """Launch the insert kernel on a staged batch WITHOUT the
        completion fence; returns the output array handle the caller
        fences on (``.block_until_ready()``) before acking the batch."""
        d_keys, d_lengths, B = staged
        with obs.phase("kernel"):
            self.words = self._insert(self.words, d_keys, d_lengths)
        self.n_inserted += B
        return self.words

    def launch_query(self, staged):
        """Launch the membership kernel on a staged batch; returns
        ``(device hits, valid count)`` — the caller's ``np.asarray`` is
        the fence + D2H. Query device work runs under its own
        ``kernel_query`` phase (ISSUE 12) so the read path's device
        time is separable from the write path's in every dashboard."""
        d_keys, d_lengths, B = staged
        self._query_launch_counter(d_keys.shape[0])
        with obs.phase("kernel_query"):
            hits = self._query(self.words, d_keys, d_lengths)
        self.n_queried += B
        return hits, B

    def _kernel_fence(self, handle) -> None:
        """Completion fence for one launched kernel (under an active
        request context). ShardedBloomFilter overrides it to record
        per-shard device-completion phases (ROADMAP 1(c))."""
        handle.block_until_ready()

    def _query_launch_counter(self, padded_batch: int) -> None:
        """Launch-mix hook (ISSUE 12): BlockedBloomFilter counts which
        membership path each query launch resolves to. No-op for
        layouts without a query-path split."""

    # fixed-width batch API (the `fixed` wire encoding's server path)

    def insert_packed(self, rows: np.ndarray) -> int:
        """Insert fixed-width pre-packed keys (``uint8[B, W]``, W <=
        key_len) — the zero-copy decode path of the ``fixed`` wire
        encoding."""
        out = self.launch_insert(self.stage_batch(rows=rows))
        if obs.fencing():
            # same honesty fence as insert_batch: under an active
            # request the kernel phase must cover real device work
            with obs.phase("kernel"):
                self._kernel_fence(out)
        return int(rows.shape[0])

    def include_packed(self, rows: np.ndarray) -> np.ndarray:
        """Membership for fixed-width pre-packed keys."""
        hits, B = self.launch_query(self.stage_batch(rows=rows))
        if obs.fencing():
            with obs.phase("kernel_query"):
                self._kernel_fence(hits)
        with obs.phase("d2h"):
            out = np.asarray(hits)
        return out[:B]

    def block_until_ready(self) -> None:
        self.words.block_until_ready()

    @property
    def words_logical(self) -> np.ndarray:
        """Host copy of the storage in its LOGICAL shape — what oracles,
        tools, and tests should compare against. For flat filters this is
        the device shape; :class:`BlockedBloomFilter` overrides it to
        undo the fat [NB/J, 128] device view (same row-major bytes)."""
        return np.asarray(self.words)

    def _set_words(self, words) -> None:
        """Replace storage from a flat array (checkpoint restore)."""
        self.words = jnp.asarray(
            np.asarray(words, dtype=np.uint32).reshape(self.words.shape)
        )

    def clear(self) -> None:
        """Reference ``#clear`` — zero the array (SURVEY.md §3.4: DEL becomes
        ``jnp.zeros_like``)."""
        self.words = jnp.zeros_like(self.words)
        self.n_inserted = 0

    # batch API (the north-star surface)

    def insert_batch(self, keys: Sequence[bytes | str]) -> None:
        keys_u8, lengths, B = self._pack_padded(keys)
        keys_u8, lengths = self._stage_batch(keys_u8, lengths)
        with obs.phase("kernel"):
            self.words = self._insert(self.words, keys_u8, lengths)
            if obs.fencing():
                # fence so the kernel phase covers real device work, not
                # just async dispatch; only under an active request (the
                # library/streaming path keeps JAX's async pipelining).
                # Cost on the server path is negligible: the per-filter
                # op lock + donation data dependence already serialize
                # same-filter work, and the gRPC hop is transport-bound
                # at ~1/50 of device rate (benchmarks grpc_path_r5)
                self._kernel_fence(self.words)
        self.n_inserted += B

    def include_batch(self, keys: Sequence[bytes | str]) -> np.ndarray:
        keys_u8, lengths, B = self._pack_padded(keys)
        keys_u8, lengths = self._stage_batch(keys_u8, lengths)
        self._query_launch_counter(keys_u8.shape[0])
        with obs.phase("kernel_query"):
            hits = self._query(self.words, keys_u8, lengths)
            if obs.fencing():
                self._kernel_fence(hits)
        with obs.phase("d2h"):
            out = np.asarray(hits)
        self.n_queried += B
        return out[:B]

    # pre-packed device-array API (bench / server / streaming path)

    def insert_arrays(self, keys_u8, lengths, *, n_valid: int | None = None) -> None:
        """``n_valid`` = true key count when the batch carries static-shape
        padding (lengths = -1 rows set no bits but must not inflate
        ``n_inserted`` — it is persisted into checkpoints)."""
        self.words = self._insert(self.words, keys_u8, lengths)
        self.n_inserted += int(keys_u8.shape[0]) if n_valid is None else n_valid

    def include_arrays(self, keys_u8, lengths):
        self.n_queried += int(keys_u8.shape[0])
        return self._query(self.words, keys_u8, lengths)

    # scalar API (reference parity)

    def insert(self, key: bytes | str) -> None:
        self.insert_batch([key])

    def include(self, key: bytes | str) -> bool:
        return bool(self.include_batch([key])[0])

    __contains__ = include

    # observability (SURVEY.md §5 metrics: fill ratio & predicted FPR;
    # the /metrics gauges in tpubloom.obs.exposition read these)

    def fill_ratio(self) -> float:
        if self.config.counting:
            raise ValueError("fill_ratio is for plain/blocked filters")
        return float(bitops.popcount_fill(self.words, self.config.m))

    def estimated_fpr(self) -> float:
        return self.fill_ratio() ** self.config.k

    def predicted_fpr(self) -> float:
        """Analytic FPR from the geometry and ``n_inserted`` alone:
        ``(1 - e^{-kn/m})^k``. Contrast with :meth:`estimated_fpr`
        (computed from the OBSERVED fill) — the gap between them is the
        ``fpr_drift`` gauge: sustained drift means the deployed key
        distribution (duplicates, adversarial keys) or a kernel
        regression is violating the sizing model the filter was
        provisioned with."""
        m, k = self.config.m, self.config.k
        if self.config.block_bits:
            # the blocked layout's own (measurement-pinned) model — using
            # the flat formula here would misread the layout's inherent
            # FPR excess at high fill as deployment drift
            from tpubloom.params import blocked_fpr

            return blocked_fpr(
                self.n_inserted,
                m=m,
                k=k,
                block_bits=self.config.block_bits,
                block_hash=self.config.block_hash,
            )
        return (1.0 - math.exp(-k * self.n_inserted / m)) ** k

    def _fpr_gauges(self) -> dict:
        """fill/bits/FPR gauge block shared by the non-counting stats()."""
        fill = self.fill_ratio()
        estimated = fill**self.config.k
        predicted = self.predicted_fpr()
        return {
            "fill_ratio": fill,
            "bits_set": int(round(fill * self.config.m)),
            "estimated_fpr": estimated,
            "predicted_fpr": predicted,
            "fpr_drift": estimated - predicted,
        }


class BloomFilter(_FilterBase):
    """Plain bloom filter on a packed ``uint32`` device array."""

    def __init__(self, config: FilterConfig):
        if config.counting:
            raise ValueError("use CountingBloomFilter for counting configs")
        super().__init__(config, config.n_words)
        self._insert = jax.jit(make_insert_fn(config), donate_argnums=0)
        self._query = jax.jit(make_query_fn(config))

    def stats(self) -> dict:
        return {
            "m": self.config.m,
            "k": self.config.k,
            "n_inserted": self.n_inserted,
            "n_queried": self.n_queried,
            **self._fpr_gauges(),
        }

    # persistence (Redis-string-bitmap format, reference-compatible)

    def to_redis_bitmap(self) -> bytes:
        return words_to_redis_bitmap(np.asarray(self.words), self.config.m)

    @classmethod
    def from_redis_bitmap(cls, config: FilterConfig, data: bytes) -> "BloomFilter":
        f = cls(config)
        f.words = jnp.asarray(redis_bitmap_to_words(data, config.m))
        return f


class BlockedBloomFilter(_FilterBase):
    """Blocked (cache-line) bloom filter — the throughput layout.

    All k bits of a key live in one ``config.block_bits``-sized block, so
    every op touches one contiguous row instead of k scattered words —
    ~k× less random HBM traffic than :class:`BloomFilter` (see
    tpubloom.ops.blocked for the measured rationale and the exact spec).
    Use when raw insert/query rate matters more than the last ~fraction of
    FPR headroom at high fill; not bit-compatible with the flat layout.

    Storage layout: ``self.words`` is the DEVICE array and, whenever
    ``blocked_storage_fat(config)`` holds, uses the fat ``[NB/J, 128]``
    view (J = 128 // words_per_block) — the SAME row-major bytes as the
    logical ``[n_blocks, words_per_block]`` array, folded J blocks per
    row so DMA runs at full 128-lane width (benchmarks/RESULTS_r3.md §2
    measured 5× on this). Read ``words_logical`` for the logical shape;
    ``to_bytes``/``from_bytes`` are layout-agnostic (row-major bytes are
    identical under both views).
    """

    def __init__(self, config: FilterConfig):
        if config.counting:
            # a counting config reinterprets m as counters (4 bits each);
            # building a plain blocked filter from it would silently use
            # the wrong geometry and drop delete support
            raise ValueError(
                "use BlockedCountingBloomFilter for counting configs"
            )
        if not config.block_bits:
            config = config.replace(block_bits=512)
        super().__init__(config, 0)  # placeholder; storage is 2-D
        # fat [NB/J, 128] storage where possible: the SAME row-major
        # bytes as [NB, W], but XLA's tiled HBM layouts DMA narrow-lane
        # arrays at ~1/5 speed and make the reshape a real copy
        # (benchmarks/RESULTS_r3.md) — so the persistent array stays fat
        # and every kernel/gather reads it natively
        self._fat = blocked_storage_fat(config)
        self.words = jnp.zeros(blocked_device_shape(config), jnp.uint32)
        self._insert = jax.jit(
            make_blocked_insert_fn(config, storage_fat=self._fat),
            donate_argnums=0,
        )
        self._query = jax.jit(
            make_blocked_query_fn(config, storage_fat=self._fat)
        )
        self._test_insert = None  # jitted lazily on first return_presence use

    def insert_batch(
        self, keys: Sequence[bytes | str], *, return_presence: bool = False
    ):
        """Insert a batch; with ``return_presence`` also report each key's
        membership BEFORE the batch (test-and-insert, one fused device
        pass on the sweep path — the reference Lua add script's
        semantics). Within-batch duplicates all report the pre-batch
        state."""
        if not return_presence:
            return super().insert_batch(keys)
        if self._test_insert is None:
            self._test_insert = jax.jit(
                make_blocked_test_insert_fn(
                    self.config, storage_fat=self._fat
                ),
                donate_argnums=0,
            )
        keys_u8, lengths, B = self._pack_padded(keys)
        keys_u8, lengths = self._stage_batch(keys_u8, lengths)
        with obs.phase("kernel"):
            self.words, present = self._test_insert(self.words, keys_u8, lengths)
            if obs.fencing():
                self._kernel_fence(present)
        with obs.phase("d2h"):
            out = np.asarray(present)
        self.n_inserted += B
        return out[:B]

    def _query_launch_counter(self, padded_batch: int) -> None:
        """Launch-mix counters (ISSUE 12): which membership path this
        launch resolves to — the same deterministic funnel the traced
        kernel used (``resolve_query_path`` is pure in (config, backend,
        padded batch shape)), counted host-side because the decision is
        made at trace time and invisible to per-launch instrumentation.
        ``query_sweep_launches`` + ``query_gather_launches`` sum to all
        blocked query launches; a nonzero gather count on a TPU host
        says batches are falling off the query kernel's envelope."""
        from tpubloom.ops import sweep

        if sweep.effective_query_path(self.config, max(1, padded_batch)) == "sweep":
            obs_counters.incr("query_sweep_launches")
        else:
            obs_counters.incr("query_gather_launches")

    @property
    def words_logical(self) -> np.ndarray:
        return np.asarray(self.words).reshape(
            self.config.n_blocks, self.config.words_per_block
        )

    def stats(self) -> dict:
        return {
            "m": self.config.m,
            "k": self.config.k,
            "block_bits": self.config.block_bits,
            "n_inserted": self.n_inserted,
            "n_queried": self.n_queried,
            **self._fpr_gauges(),
        }

    # persistence (raw little-endian words, row-major; NOT the Redis bitmap
    # format — blocked arrays are a different position spec)

    def to_bytes(self) -> bytes:
        return np.asarray(self.words).astype("<u4").tobytes()

    @classmethod
    def from_bytes(cls, config: FilterConfig, data: bytes) -> "BlockedBloomFilter":
        f = cls(config)
        arr = np.frombuffer(data, dtype="<u4").astype(np.uint32)
        f.words = jnp.asarray(arr.reshape(f.words.shape))
        return f


class BlockedCountingBloomFilter(_FilterBase):
    """Blocked (cache-line) counting filter — delete support at the
    blocked layout's throughput.

    All k 4-bit counters of a key live in one ``block_bits``-bit block
    (``block_bits/4`` counters), so updates/queries touch one contiguous
    row instead of k scattered words; on TPU the insert/delete hot loop
    runs as the Pallas counting sweep (``tpubloom.ops.sweep``). ``m``
    counts COUNTERS, as in :class:`CountingBloomFilter`. Same saturation
    semantics (increments clamp at 15, decrements floor at 0, one clamp
    per batch against the pre-batch value).
    """

    def __init__(self, config: FilterConfig):
        if not config.counting:
            config = config.replace(counting=True)
        if not config.block_bits:
            config = config.replace(block_bits=512)
        if config.m >= (1 << 31):
            raise ValueError("counting filters support m < 2^31")
        super().__init__(config, 0)  # storage is 2-D
        # fat [NB/J, 128] storage where possible, like BlockedBloomFilter
        # (same row-major bytes as [NB, W]; 128-lane DMA tier)
        self._fat = blocked_storage_fat(config)
        self.words = jnp.zeros(blocked_device_shape(config), jnp.uint32)
        self._insert = jax.jit(
            make_blocked_counter_fn(
                config, increment=True, storage_fat=self._fat
            ),
            donate_argnums=0,
        )
        self._delete = jax.jit(
            make_blocked_counter_fn(
                config, increment=False, storage_fat=self._fat
            ),
            donate_argnums=0,
        )
        self._query = jax.jit(
            make_blocked_counting_query_fn(config, storage_fat=self._fat)
        )

    @property
    def words_logical(self) -> np.ndarray:
        return np.asarray(self.words).reshape(
            self.config.n_blocks, self.config.words_per_block
        )

    def delete_batch(self, keys: Sequence[bytes | str]) -> None:
        keys_u8, lengths, B = self._pack_padded(keys)
        self.words = self._delete(self.words, keys_u8, lengths)
        self.n_inserted = max(0, self.n_inserted - B)

    def delete(self, key: bytes | str) -> None:
        self.delete_batch([key])

    def stats(self) -> dict:
        return {
            "m": self.config.m,
            "k": self.config.k,
            "block_bits": self.config.block_bits,
            "n_inserted": self.n_inserted,
            "n_queried": self.n_queried,
        }

    def to_bytes(self) -> bytes:
        return np.asarray(self.words).astype("<u4").tobytes()

    @classmethod
    def from_bytes(
        cls, config: FilterConfig, data: bytes
    ) -> "BlockedCountingBloomFilter":
        f = cls(config)
        arr = np.frombuffer(data, dtype="<u4").astype(np.uint32)
        f.words = jnp.asarray(arr.reshape(f.words.shape))
        return f


class CountingBloomFilter(_FilterBase):
    """Counting bloom filter: 4-bit saturating counters, supports delete."""

    def __init__(self, config: FilterConfig):
        if not config.counting:
            config = config.replace(counting=True)
        if config.m >= (1 << 31):
            raise ValueError("counting filters support m < 2^31 (config 4: m=2^30)")
        super().__init__(config, config.n_counter_words)
        self._insert = jax.jit(make_counter_fn(config, increment=True), donate_argnums=0)
        self._delete = jax.jit(make_counter_fn(config, increment=False), donate_argnums=0)
        self._query = jax.jit(make_counting_query_fn(config))

    def delete_batch(self, keys: Sequence[bytes | str]) -> None:
        keys_u8, lengths, B = self._pack_padded(keys)
        self.words = self._delete(self.words, keys_u8, lengths)
        self.n_inserted = max(0, self.n_inserted - B)

    def delete(self, key: bytes | str) -> None:
        self.delete_batch([key])

    def to_bytes(self) -> bytes:
        return np.asarray(self.words).astype("<u4").tobytes()

    @classmethod
    def from_bytes(cls, config: FilterConfig, data: bytes) -> "CountingBloomFilter":
        f = cls(config)
        f.words = jnp.asarray(np.frombuffer(data, dtype="<u4").astype(np.uint32))
        return f
