"""ShardedBloomFilter — a filter array sharded across a TPU device mesh.

Parity: BASELINE config 5 — "64-shard filter array over v5e-8, m=2^36 total
— pmap hash + all-reduce-OR cross-chip membership". The reference gem has no
multi-node story (a single Redis instance is its whole world, SURVEY.md
§2.2); sharding across Redis instances is something its users bolt on
client-side. Here it is a first-class component.

Design (routed layout, SURVEY.md §3.5):

* The m-bit array is split into ``n_shards`` independent sub-filters of
  ``m_local = m / n_shards`` bits, laid out ``[n_shards, n_words_local]``
  and sharded over the mesh axis ``"shards"`` — shard s lives in chip s's
  HBM (1 GiB/chip at m=2^36 over 8 chips).
* Every chip hashes the **full** replicated batch (hashing is cheap VPU
  work; replicating it avoids an all-to-all of raw keys — the scaling-book
  move of trading redundant compute for collective traffic). A routing hash
  assigns each key to exactly one shard; a chip scatter-ORs only the keys it
  owns and drops the rest, so the whole k-position group of a key is local
  to one chip.
* Membership: each chip evaluates the gather-AND verdict for its owned keys;
  a single ``psum`` over the ``shards`` axis (all-reduce-OR of one-hot
  verdicts — rides the ICI) assembles the replicated ``bool[B]`` answer.
  One small collective per batch, O(B) bytes, no raw-key movement.
* Insert races are benign (scatter-OR commutes); routing is deterministic,
  so the same key always lands on the same chip.

The same code runs on a real v5e-8 and on the fake 8-device CPU backend
(``xla_force_host_platform_device_count``) used in tests and by the
driver's ``dryrun_multichip``.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpubloom import faults
from tpubloom.config import FilterConfig
from tpubloom.filter import _FilterBase
from tpubloom.obs import context as obs
from tpubloom.ops import bitops, blocked, counting, hashing
from tpubloom.utils.packing import redis_bitmap_to_words, words_to_redis_bitmap

AXIS = "shards"

log = logging.getLogger(__name__)


def make_mesh(n_shards: int, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D device mesh over the ``shards`` axis.

    ``n_shards`` may exceed the device count if it divides evenly — each
    device then hosts several logical shards (how 64 shards map onto 8
    chips in config 5: 8 shard-rows per chip).
    """
    if devices is None:
        devices = jax.devices()
    n_dev = len(devices)
    if n_shards % n_dev != 0 and n_dev % n_shards != 0:
        raise ValueError(f"n_shards={n_shards} incompatible with {n_dev} devices")
    use = devices[: min(n_shards, n_dev)]
    return Mesh(np.array(use), (AXIS,))


def _route_local(config: FilterConfig, shards_per_dev: int, keys_u8, lengths):
    """The one routing decision every sharded op shares: hash the
    replicated batch with the routing hash, map the owning shard to this
    device's local row space. Returns ``(local_row[B], owned[B],
    lens[B])`` — ``owned`` marks keys routed to one of this device's
    shard rows (False for batch padding); ``local_row`` is meaningful
    only where owned (callers clamp with ``jnp.where(owned, ...)``)."""
    dev = jax.lax.axis_index(AXIS)
    lens = jnp.maximum(lengths, 0)
    route = hashing.route_shards(
        keys_u8, lens, n_shards=config.shards, seed=config.seed
    ).astype(jnp.int32)
    local_row = route - dev * shards_per_dev
    owned = (local_row >= 0) & (local_row < shards_per_dev) & (lengths >= 0)
    return local_row, owned, lens


def _use_local_sweep(
    config: FilterConfig, local_rows: int, batch: int, *,
    presence: bool = False,
) -> bool:
    """Resolve config.insert_path for the per-device hot loop (the local
    row count, not the global filter, decides sweep applicability) —
    delegates to the single resolve_insert_path funnel. ``batch`` must be
    the EXPECTED OWNED count (~B / n_dev), matching the window-sizing
    call: resolving with the full replicated batch would overstate
    per-device occupancy by n_dev× and let a globally-dense but
    per-device-sparse batch stream the whole local block array for a
    handful of owned rows."""
    from tpubloom.ops import sweep

    return (
        sweep.resolve_insert_path(
            config, batch, presence=presence, n_blocks=local_rows
        )
        == "sweep"
    )


def _routed_positions(config: FilterConfig, shards_per_dev: int, keys_u8, lengths):
    """Shared insert/query preamble: hash the replicated batch, route each
    key, and translate to this device's local (word, bit) coordinates.

    Returns ``(word[B, k], bit[B, k], owned[B])`` where ``owned`` marks keys
    routed to one of this device's shard rows (False for padding) and
    ``word`` is clamped to row 0 for unowned keys (callers mask with
    ``owned`` — scatter drops them, gather verdicts are ignored).
    """
    m_local = config.m_per_shard
    local_row, owned, lens = _route_local(config, shards_per_dev, keys_u8, lengths)
    ph, pl = hashing.positions(
        keys_u8, lens, m=m_local, k=config.k, seed=config.seed
    )
    word, bit = hashing.split_word_bit(ph, pl)
    # Global->local row: shard r is row (r - dev*shards_per_dev) here.
    word = word + jnp.where(owned, local_row, 0)[:, None] * (m_local // 32)
    return word, bit, owned


def make_sharded_insert_fn(config: FilterConfig, mesh: Mesh):
    """``(words[S, W], keys[B, L], lengths[B]) -> words`` over the mesh.

    ``words`` is sharded over ``shards``; keys/lengths are replicated.
    """
    shards_per_dev = config.shards // mesh.devices.size

    def local_insert(words_block, keys_u8, lengths):
        # words_block: [shards_per_dev, n_words_local] — this device's rows.
        word, bit, owned = _routed_positions(
            config, shards_per_dev, keys_u8, lengths
        )
        flat = words_block.reshape(-1)
        valid_k = jnp.broadcast_to(owned[:, None], word.shape)
        flat = bitops.scatter_or(flat, word.ravel(), bit.ravel(), valid_k.ravel())
        return flat.reshape(words_block.shape)

    return shard_map(
        local_insert,
        mesh=mesh,
        in_specs=(P(AXIS, None), P(), P()),
        out_specs=P(AXIS, None),
    )


def make_sharded_query_fn(config: FilterConfig, mesh: Mesh):
    """``(words[S, W], keys[B, L], lengths[B]) -> bool[B]`` (replicated).

    Each chip answers for the keys it owns; ``psum`` over the shards axis
    (all-reduce-OR of disjoint one-hot verdicts) assembles the full answer.
    """
    shards_per_dev = config.shards // mesh.devices.size

    def local_query(words_block, keys_u8, lengths):
        word, bit, owned = _routed_positions(
            config, shards_per_dev, keys_u8, lengths
        )
        verdict = bitops.query_membership(words_block.reshape(-1), word, bit)
        one_hot = jnp.where(owned, verdict, False).astype(jnp.uint32)
        hit = jax.lax.psum(one_hot, AXIS)  # all-reduce-OR over ICI
        return hit > 0

    return shard_map(
        local_query,
        mesh=mesh,
        in_specs=(P(AXIS, None), P(), P()),
        out_specs=P(),
    )


def local_blocked_storage_fat(config: FilterConfig) -> bool:
    """Whether the sharded blocked storage keeps each shard's rows in the
    fat [NBL*W/128, 128] view (mirrors filter.blocked_storage_fat on the
    PER-SHARD geometry — mesh-size independent, because fat rows never
    straddle a shard boundary when NBL % J == 0). Applies to plain and
    counting blocked layouts; the per-device hot loop then runs the
    fat-row kernels at the 128-lane DMA tier (VERDICT r3 #3)."""
    if not config.block_bits:
        return False
    w = config.words_per_block
    return 128 % w == 0 and config.n_blocks_per_shard % (128 // w) == 0


def sharded_blocked_shape(config: FilterConfig) -> tuple[int, int, int]:
    """Global device-array shape for sharded blocked storage (plain or
    counting): per-shard fat rows when :func:`local_blocked_storage_fat`
    holds, else logical rows. The ONE place the sharded fat geometry is
    spelled out (ShardedBloomFilter and the driver dryrun both use it)."""
    if local_blocked_storage_fat(config):
        return (
            config.shards,
            config.n_blocks_per_shard * config.words_per_block // 128,
            128,
        )
    return (config.shards, config.n_blocks_per_shard, config.words_per_block)


def _routed_blocks(
    config: FilterConfig, shards_per_dev: int, keys_u8, lengths, *, want_bit=False
):
    """Blocked-layout preamble: route keys to shards, then to this device's
    local block rows. Returns ``(blk[B], masks[B, W], owned[B])`` (plus the
    raw in-block positions when ``want_bit`` — the sweep path re-sorts and
    rebuilds masks itself) with ``blk`` indexing the device-local
    ``[shards_per_dev * n_blocks_local]`` row space (clamped to 0 for
    unowned keys)."""
    nbl = config.n_blocks_per_shard
    local_row, owned, lens = _route_local(config, shards_per_dev, keys_u8, lengths)
    blk, bit = blocked.block_positions(
        keys_u8, lens,
        n_blocks=nbl, block_bits=config.block_bits, k=config.k,
        seed=config.seed, block_hash=config.block_hash,
    )
    masks = blocked.build_masks(bit, config.words_per_block)
    blk = blk + jnp.where(owned, local_row, 0) * nbl
    if want_bit:
        return blk, masks, owned, bit
    return blk, masks, owned


def make_sharded_blocked_insert_fn(config: FilterConfig, mesh: Mesh):
    """Blocked-layout sharded insert: ``(blocks[S, NBL, W], keys, lengths)``
    with ``blocks`` sharded over ``shards``; one row RMW per owned key.
    On TPU the per-device hot loop runs the Pallas partition sweep
    (pallas_call inside shard_map) when the local shape qualifies."""
    shards_per_dev = config.shards // mesh.devices.size
    local_rows = shards_per_dev * config.n_blocks_per_shard

    fat_store = local_blocked_storage_fat(config)
    n_dev = mesh.devices.size
    w = config.words_per_block

    def local_insert(blocks_block, keys_u8, lengths):
        from tpubloom.ops import sweep

        # blocks_block: [shards_per_dev, n_blocks_local, W] logical or
        # [shards_per_dev, NBL*W/128, 128] fat — this device's rows.
        B = keys_u8.shape[0]
        blk, masks, owned, bit = _routed_blocks(
            config, shards_per_dev, keys_u8, lengths, want_bit=True
        )
        use_sweep = _use_local_sweep(config, local_rows, max(1, B // n_dev))
        if fat_store:
            flat = blocks_block.reshape(-1, 128)  # [spd*NBLJ, 128]
            # window sizing uses the EXPECTED owned count (~B/n_dev):
            # sizing for the full replicated batch would inflate KJ by
            # n_dev x; per-window occupancy of owned keys is Poisson, so
            # lam+8sigma of B/n_dev covers it (overflow -> scatter
            # fallback inside apply_fat_updates keeps skew correct)
            fat_params = (
                sweep.choose_fat_params(local_rows, max(1, B // n_dev), w)
                if use_sweep
                else None
            )
            if fat_params is not None:
                out = sweep.apply_fat_updates(
                    flat, blk, bit, owned, block_bits=config.block_bits,
                    params=fat_params, storage_fat=True,
                )
                return out.reshape(blocks_block.shape)
            if use_sweep:
                # legacy kernel needs the logical view (reshape copy —
                # only shapes the fat chooser rejects land here)
                out = sweep.apply_blocked_updates(
                    flat.reshape(-1, w), blk, bit, owned,
                    block_bits=config.block_bits,
                )
                return out.reshape(blocks_block.shape)
            frow, m128 = blocked.fat_fold_masks(blk, masks, 128 // w)
            out = blocked.blocked_insert(flat, frow, m128, owned)
            return out.reshape(blocks_block.shape)
        flat = blocks_block.reshape(-1, w)
        if use_sweep:
            flat = sweep.apply_blocked_updates(
                flat, blk, bit, owned, block_bits=config.block_bits
            )
        else:
            flat = blocked.blocked_insert(flat, blk, masks, owned)
        return flat.reshape(blocks_block.shape)

    return shard_map(
        local_insert,
        mesh=mesh,
        in_specs=(P(AXIS, None, None), P(), P()),
        out_specs=P(AXIS, None, None),
        # pallas_call outputs cannot carry vma metadata; the local insert
        # has no collectives, so the varying-axes lint has nothing to check
        check_vma=False,
    )


def make_sharded_blocked_query_fn(config: FilterConfig, mesh: Mesh):
    """Blocked-layout sharded membership with the same psum-OR assembly as
    the flat path: owners answer, ICI all-reduce merges.

    On TPU the per-device verdicts ride the read-only query sweep kernel
    (ISSUE 12) when the LOCAL shape qualifies and ``shards_per_dev == 1``:
    every key (owned or not) then queries its natural in-shard block row
    on every device — the occupancy stays uniform over the local rows,
    the sweep's tail-suffix presence contract holds (``lengths >= 0`` is
    tail padding), and unowned keys' garbage verdicts are masked by
    ``owned`` before the psum, exactly as the gather path masks them.
    With several shards per device the unowned keys would pile onto
    shard-row 0's windows (n_dev× the sized occupancy → perpetual
    overflow fallback), so those geometries keep the gather."""
    shards_per_dev = config.shards // mesh.devices.size
    local_rows = shards_per_dev * config.n_blocks_per_shard

    fat_store = local_blocked_storage_fat(config)
    w = config.words_per_block

    def local_query(blocks_block, keys_u8, lengths):
        from tpubloom.ops import sweep

        B = keys_u8.shape[0]
        blk, masks, owned, bit = _routed_blocks(
            config, shards_per_dev, keys_u8, lengths, want_bit=True
        )
        if fat_store and shards_per_dev == 1 and (
            sweep.resolve_query_path(config, B, n_blocks=local_rows)
            == "sweep"
        ):
            # window sizing uses the FULL batch: with spd == 1 every key
            # lands at its in-shard row on every device (blk is already
            # local — `owned` adds 0), so per-window occupancy covers B,
            # same as the gather path's B-row gather per device
            params = sweep.choose_fat_query_params(local_rows, B, w)
            if params is not None:
                flat = blocks_block.reshape(-1, 128)
                verdict = sweep.apply_fat_query(
                    flat, blk, bit, lengths >= 0,
                    block_bits=config.block_bits, params=params,
                    storage_fat=True,
                )
                one_hot = jnp.where(owned, verdict, False).astype(jnp.uint32)
                return jax.lax.psum(one_hot, AXIS) > 0
        if fat_store:
            flat = blocks_block.reshape(-1, 128)
            verdict = blocked.fat_blocked_query(flat, blk, masks)
        else:
            flat = blocks_block.reshape(-1, w)
            verdict = blocked.blocked_query(flat, blk, masks)
        one_hot = jnp.where(owned, verdict, False).astype(jnp.uint32)
        hit = jax.lax.psum(one_hot, AXIS)
        return hit > 0

    return shard_map(
        local_query,
        mesh=mesh,
        in_specs=(P(AXIS, None, None), P(), P()),
        out_specs=P(),
        # pallas_call outputs carry no vma metadata (see blocked insert);
        # the psum still assembles the replicated verdict either way
        check_vma=False,
    )


# -- counting variant (configs 4 x 5: sharded counting filter array) ---------


def _routed_counter_positions(config: FilterConfig, shards_per_dev, keys_u8, lengths):
    """Flat-counting preamble: route keys, then device-local counter
    positions. ``m`` counts COUNTERS; shard s owns counters
    ``[s*m_local, (s+1)*m_local)``. Returns ``(pos[B, k], owned[B])`` with
    ``pos`` in this device's ``[0, shards_per_dev*m_local)`` local space
    (row 0 for unowned keys — callers mask)."""
    m_local = config.m_per_shard
    local_row, owned, lens = _route_local(config, shards_per_dev, keys_u8, lengths)
    _, pl = hashing.positions(
        keys_u8, lens, m=m_local, k=config.k, seed=config.seed
    )
    pos = pl.astype(jnp.int32) + jnp.where(owned, local_row, 0)[:, None] * m_local
    return pos, owned


def make_sharded_counter_fn(config: FilterConfig, mesh: Mesh, *, increment: bool):
    """Flat-counting sharded update: ``(words[S, Wc], keys, lengths) ->
    words`` with saturating +1 (insert) / flooring -1 (delete) on this
    device's packed 4-bit counters — same one-clamp-per-batch semantics
    as :func:`tpubloom.ops.counting.counter_update` (the ground truth)."""
    shards_per_dev = config.shards // mesh.devices.size

    def local_update(words_block, keys_u8, lengths):
        pos, owned = _routed_counter_positions(
            config, shards_per_dev, keys_u8, lengths
        )
        valid_k = jnp.broadcast_to(owned[:, None], pos.shape)
        flat = counting.counter_update(
            words_block.reshape(-1), pos.ravel(), valid_k.ravel(),
            increment=increment,
        )
        return flat.reshape(words_block.shape)

    return shard_map(
        local_update,
        mesh=mesh,
        in_specs=(P(AXIS, None), P(), P()),
        out_specs=P(AXIS, None),
    )


def make_sharded_counting_query_fn(config: FilterConfig, mesh: Mesh):
    """Flat-counting sharded membership: owners test all-k-counters
    nonzero, psum-OR over ICI assembles the replicated verdict."""
    shards_per_dev = config.shards // mesh.devices.size

    def local_query(words_block, keys_u8, lengths):
        pos, owned = _routed_counter_positions(
            config, shards_per_dev, keys_u8, lengths
        )
        verdict = counting.counting_membership(words_block.reshape(-1), pos)
        one_hot = jnp.where(owned, verdict, False).astype(jnp.uint32)
        hit = jax.lax.psum(one_hot, AXIS)
        return hit > 0

    return shard_map(
        local_query,
        mesh=mesh,
        in_specs=(P(AXIS, None), P(), P()),
        out_specs=P(),
    )


def _routed_counter_blocks(config: FilterConfig, shards_per_dev, keys_u8, lengths):
    """Blocked-counting preamble: route keys to shards, then to this
    device's local block rows. Returns ``(blk[B], cpos[B, k], owned[B])``
    with ``blk`` in the device-local ``[shards_per_dev * n_blocks_local]``
    row space and ``cpos`` the in-block counter positions."""
    nbl = config.n_blocks_per_shard
    local_row, owned, lens = _route_local(config, shards_per_dev, keys_u8, lengths)
    blk, cpos = blocked.block_positions(
        keys_u8, lens,
        n_blocks=nbl, block_bits=config.counters_per_block, k=config.k,
        seed=config.seed, block_hash=config.block_hash,
    )
    blk = blk + jnp.where(owned, local_row, 0) * nbl
    return blk, cpos, owned


def make_sharded_blocked_counter_fn(
    config: FilterConfig, mesh: Mesh, *, increment: bool
):
    """Blocked-counting sharded update; on TPU the per-device hot loop is
    the Pallas counting sweep (``sweep.apply_counter_updates`` inside
    shard_map), elsewhere the sorted-scan flat-counting kernel on the
    raveled local array — bit-identical results either way."""
    shards_per_dev = config.shards // mesh.devices.size
    local_rows = shards_per_dev * config.n_blocks_per_shard
    cpb = config.counters_per_block

    fat_store = local_blocked_storage_fat(config)
    n_dev = mesh.devices.size
    w = config.words_per_block

    def local_update(blocks_block, keys_u8, lengths):
        from tpubloom.ops import sweep

        B = keys_u8.shape[0]
        blk, cpos, owned = _routed_counter_blocks(
            config, shards_per_dev, keys_u8, lengths
        )
        use_sweep = _use_local_sweep(config, local_rows, max(1, B // n_dev))
        if use_sweep and config.k > 15:
            if config.insert_path == "sweep":
                # match the single-chip contract (filter.py): a forced
                # sweep must not silently run the scatter path
                raise ValueError(
                    "counting sweep supports k <= 15 — use "
                    "insert_path='scatter'"
                )
            use_sweep = False
        if fat_store:
            flat = blocks_block.reshape(-1, 128)
            fat_params = (
                sweep.choose_fat_params(
                    local_rows, max(1, B // n_dev), w, counting=True
                )
                if use_sweep
                else None
            )
            if fat_params is not None:
                out = sweep.apply_fat_counter_updates(
                    flat, blk, cpos, owned,
                    counters_per_block=cpb, k=config.k, increment=increment,
                    params=fat_params, storage_fat=True,
                )
                return out.reshape(blocks_block.shape)
            if use_sweep:
                out = sweep.apply_counter_updates(
                    flat.reshape(-1, w), blk, cpos, owned,
                    counters_per_block=cpb, k=config.k, increment=increment,
                )
                return out.reshape(blocks_block.shape)
            # flat scatter fallback: the raveled fat bytes ARE the
            # raveled logical bytes — no fold or reshape copy needed
            gpos = (blk[:, None] * cpb + cpos.astype(jnp.int32)).astype(
                jnp.int32
            )
            valid_k = jnp.broadcast_to(owned[:, None], gpos.shape)
            out = counting.counter_update(
                flat.reshape(-1), gpos.ravel(), valid_k.ravel(),
                increment=increment,
            )
            return out.reshape(blocks_block.shape)
        flat = blocks_block.reshape(-1, w)
        if use_sweep:
            flat = sweep.apply_counter_updates(
                flat, blk, cpos, owned,
                counters_per_block=cpb, k=config.k, increment=increment,
            )
            return flat.reshape(blocks_block.shape)
        gpos = (blk[:, None] * cpb + cpos.astype(jnp.int32)).astype(jnp.int32)
        valid_k = jnp.broadcast_to(owned[:, None], gpos.shape)
        out = counting.counter_update(
            flat.reshape(-1), gpos.ravel(), valid_k.ravel(),
            increment=increment,
        )
        return out.reshape(blocks_block.shape)

    return shard_map(
        local_update,
        mesh=mesh,
        in_specs=(P(AXIS, None, None), P(), P()),
        out_specs=P(AXIS, None, None),
        # pallas_call outputs carry no vma metadata (see blocked insert)
        check_vma=False,
    )


def make_sharded_blocked_counting_query_fn(config: FilterConfig, mesh: Mesh):
    """Blocked-counting sharded membership: one local row gather per owned
    key + all-counters-nonzero, psum-OR assembly."""
    shards_per_dev = config.shards // mesh.devices.size
    cpb = config.counters_per_block

    fat_store = local_blocked_storage_fat(config)
    w = config.words_per_block

    def local_query(blocks_block, keys_u8, lengths):
        blk, cpos, owned = _routed_counter_blocks(
            config, shards_per_dev, keys_u8, lengths
        )
        if fat_store:
            flat = blocks_block.reshape(-1, 128)
            verdict = counting.fat_blocked_counting_membership(
                flat, blk, cpos, w
            )
        else:
            flat = blocks_block.reshape(-1, w)
            verdict = counting.blocked_counting_membership(flat, blk, cpos)
        one_hot = jnp.where(owned, verdict, False).astype(jnp.uint32)
        hit = jax.lax.psum(one_hot, AXIS)
        return hit > 0

    return shard_map(
        local_query,
        mesh=mesh,
        in_specs=(P(AXIS, None, None), P(), P()),
        out_specs=P(),
    )


class ShardedBloomFilter(_FilterBase):
    """Filter array over a device mesh (config 5). API-compatible with
    :class:`tpubloom.filter.BloomFilter`."""

    def __init__(
        self,
        config: FilterConfig,
        mesh: Optional[Mesh] = None,
        devices: Optional[Sequence[jax.Device]] = None,
    ):
        if config.shards < 2:
            raise ValueError("ShardedBloomFilter needs config.shards >= 2")
        if config.counting and config.m >= (1 << 31):
            raise ValueError("counting filters support m < 2^31")
        self.mesh = mesh if mesh is not None else make_mesh(config.shards, devices)
        if config.shards % self.mesh.devices.size != 0:
            raise ValueError(
                f"shards={config.shards} must be a multiple of mesh size "
                f"{self.mesh.devices.size}"
            )
        super().__init__(config, 0)  # words set below with explicit sharding
        # per-shard fat [NBL*W/128, 128] storage where the shard geometry
        # allows (same row-major bytes per shard; 128-lane DMA tier for
        # the per-device hot loop — see filter.BlockedBloomFilter)
        self._fat = local_blocked_storage_fat(config)
        if config.counting and config.block_bits:
            self.sharding = NamedSharding(self.mesh, P(AXIS, None, None))
            self.words = jax.device_put(
                jnp.zeros(sharded_blocked_shape(config), jnp.uint32),
                self.sharding,
            )
            self._insert = jax.jit(
                make_sharded_blocked_counter_fn(config, self.mesh, increment=True),
                donate_argnums=0,
            )
            self._delete = jax.jit(
                make_sharded_blocked_counter_fn(config, self.mesh, increment=False),
                donate_argnums=0,
            )
            self._query = jax.jit(
                make_sharded_blocked_counting_query_fn(config, self.mesh)
            )
        elif config.counting:
            self.sharding = NamedSharding(self.mesh, P(AXIS, None))
            self.words = jax.device_put(
                jnp.zeros(
                    (config.shards, config.n_counter_words // config.shards),
                    jnp.uint32,
                ),
                self.sharding,
            )
            self._insert = jax.jit(
                make_sharded_counter_fn(config, self.mesh, increment=True),
                donate_argnums=0,
            )
            self._delete = jax.jit(
                make_sharded_counter_fn(config, self.mesh, increment=False),
                donate_argnums=0,
            )
            self._query = jax.jit(
                make_sharded_counting_query_fn(config, self.mesh)
            )
        elif config.block_bits:
            self.sharding = NamedSharding(self.mesh, P(AXIS, None, None))
            self.words = jax.device_put(
                jnp.zeros(sharded_blocked_shape(config), jnp.uint32),
                self.sharding,
            )
            self._insert = jax.jit(
                make_sharded_blocked_insert_fn(config, self.mesh), donate_argnums=0
            )
            self._query = jax.jit(make_sharded_blocked_query_fn(config, self.mesh))
        else:
            self.sharding = NamedSharding(self.mesh, P(AXIS, None))
            self.words = jax.device_put(
                jnp.zeros((config.shards, config.n_words_per_shard), jnp.uint32),
                self.sharding,
            )
            self._insert = jax.jit(
                make_sharded_insert_fn(config, self.mesh), donate_argnums=0
            )
            self._query = jax.jit(make_sharded_query_fn(config, self.mesh))
        for shard in self.words.addressable_shards:
            rows = range(config.shards)[shard.index[0]]
            log.info(
                "shard rows %d-%d on %s", rows[0], rows[-1], shard.device
            )

    def clear(self) -> None:
        self.words = jax.device_put(jnp.zeros_like(self.words), self.sharding)
        self.n_inserted = 0

    # -- per-shard fault points (ISSUE 4 satellite) --------------------------

    def _fire_shard_faults_packed(self, point: str, keys_u8, lengths) -> None:
        """Chaos hook over ALREADY-PACKED host arrays: fire ``point``
        once per shard this batch routes to, with ``shard=<index>``
        context — an armed ``shard=N`` predicate turns it into a
        PARTIAL failure (batches that touch shard N fail, everything
        else proceeds). Disarmed cost is one dict lookup; the host-side
        routing hash only runs while armed. This is the staged/packed
        paths' hook (ISSUE 11: lifting the coalescer exclusion required
        every sharded entry point, not just the list-path overrides, to
        keep the ``shard.*`` chaos surface)."""
        if not faults.is_armed(point):
            return
        lengths = np.asarray(lengths)
        routes = np.asarray(
            hashing.route_shards(
                jnp.asarray(keys_u8),
                jnp.asarray(np.maximum(lengths, 0)),
                n_shards=self.config.shards,
                seed=self.config.seed,
            )
        )
        touched = sorted(
            {int(s) for s, ln in zip(routes, lengths) if ln >= 0}
        )
        for shard in touched:
            faults.fire(point, shard=shard)

    def _fire_shard_faults(self, point: str, keys) -> None:
        """List-path chaos hook — packs, then routes (see
        :meth:`_fire_shard_faults_packed`)."""
        if not faults.is_armed(point):
            return
        keys_u8, lengths, _ = self._pack_padded(keys)
        self._fire_shard_faults_packed(point, keys_u8, lengths)

    def insert_batch(self, keys, **kwargs):
        self._fire_shard_faults("shard.insert", keys)
        return super().insert_batch(keys, **kwargs)

    def include_batch(self, keys):
        self._fire_shard_faults("shard.query", keys)
        return super().include_batch(keys)

    # -- per-device phase metrics (ISSUE 12 satellite, ROADMAP 1(c)) ---------

    def _kernel_fence(self, handle) -> None:
        """Break the single ``kernel``/``kernel_query`` span into
        per-shard device timings on the direct (per-request) path: fence
        each addressable shard in turn, recording a ``kernel_shard<i>``
        phase measured from the fence start — shard i's span is the
        time by which shards 0..i had all completed (the fences run
        sequentially over concurrently-executing devices), so the spans
        are monotone and the first big JUMP names the straggler device.
        Runs ONLY under an active request
        context (the library/bench paths keep the single fence;
        coalesced flushes fence on the dispatcher, which carries no
        request context — the per-flush span stays whole there, as
        before)."""
        import time

        ctx = obs.current()
        shards = getattr(handle, "addressable_shards", None)
        if ctx is None or not shards or len(shards) <= 1:
            handle.block_until_ready()
            return
        t0 = time.perf_counter()
        for i, sh in enumerate(shards):
            sh.data.block_until_ready()
            ctx.add_phase(f"kernel_shard{i}", time.perf_counter() - t0)
        handle.block_until_ready()

    # -- staged / packed surface (ISSUE 11) ----------------------------------
    #
    # The single-chip staged pipeline (filter._FilterBase.stage_batch /
    # launch_insert / launch_query) applies to the mesh unchanged — the
    # jitted shard_map kernels take the same (keys_u8, lengths) operands
    # — but the server excluded sharded filters from it (PR 10) because
    # the raw launches would bypass the per-shard ``shard.*`` fault
    # points above. These overrides restore that chaos surface: the
    # staged tuple carries the HOST arrays alongside the device handles,
    # and every launch fires the routed fault points before dispatch.
    # Staging also replicates the batch across the mesh explicitly
    # (device_put under the h2d phase), so the replication transfer
    # happens while the PREVIOUS flush's kernel is still in flight —
    # the coalescer's double buffering, mesh edition.

    #: tells the server's ``_staged_ok`` gate that the staged/packed
    #: fast paths preserve this filter's fault-point semantics
    staged_fault_points = True

    def _stage_batch(self, keys_u8, lengths):
        """Replicated H2D: place the batch on every mesh device now,
        split from the shard_map launch (the base class's single-device
        ``jnp.asarray`` would defer the broadcast into the launch)."""
        with obs.phase("h2d"):
            rep = NamedSharding(self.mesh, P())
            return (
                jax.device_put(np.ascontiguousarray(keys_u8), rep),
                jax.device_put(np.ascontiguousarray(lengths), rep),
            )

    def stage_batch(self, keys=None, *, rows=None):
        """Staged batch that ALSO carries the packed host arrays — the
        launch-side fault hooks route them without a second packing
        pass. Opaque to callers (launch_* unpack it)."""
        if rows is not None:
            keys_u8, lengths, B = self._prep_packed(np.asarray(rows, np.uint8))
        else:
            keys_u8, lengths, B = self._pack_padded(keys)
        d_keys, d_lengths = self._stage_batch(keys_u8, lengths)
        return d_keys, d_lengths, B, keys_u8, lengths

    def launch_insert(self, staged):
        d_keys, d_lengths, B, keys_u8, lengths = staged
        self._fire_shard_faults_packed("shard.insert", keys_u8, lengths)
        return super().launch_insert((d_keys, d_lengths, B))

    def launch_query(self, staged):
        d_keys, d_lengths, B, keys_u8, lengths = staged
        self._fire_shard_faults_packed("shard.query", keys_u8, lengths)
        return super().launch_query((d_keys, d_lengths, B))

    # delete (counting configs only — configs 4 x 5)

    def delete_batch(self, keys) -> None:
        if not self.config.counting:
            raise ValueError("delete requires a counting config")
        self._fire_shard_faults("shard.delete", keys)
        keys_u8, lengths, B = self._pack_padded(keys)
        self.words = self._delete(self.words, keys_u8, lengths)
        self.n_inserted = max(0, self.n_inserted - B)

    def delete(self, key) -> None:
        self.delete_batch([key])

    def shard_fill_ratios(self) -> Optional[list]:
        """Per-shard fraction of set bits (None for counting configs) —
        the /metrics ``tpubloom_shard_fill_ratio{filter,shard}`` gauge.
        Routing-skew triage: shards fill ~uniformly under the routing
        hash, so one shard running hot means a key-distribution problem
        (or a routing regression) that the GLOBAL fill ratio averages
        away. One device reduction, O(shards) bytes D2H."""
        if self.config.counting:
            return None
        per_word = jax.lax.population_count(
            self.words.reshape(self.config.shards, -1)
        )
        # float32 accumulator, same tradeoff as bitops.popcount_fill:
        # no uint32 overflow at m_per_shard > 2^32 bits, gauge-grade
        # precision
        counts = np.asarray(jnp.sum(per_word.astype(jnp.float32), axis=1))
        return [float(c) / self.config.m_per_shard for c in counts]

    def stats(self) -> dict:
        base = {
            "m": self.config.m,
            "k": self.config.k,
            "shards": self.config.shards,
            "devices": int(self.mesh.devices.size),
            "n_inserted": self.n_inserted,
            "n_queried": self.n_queried,
        }
        if self.config.counting:
            return base
        # one per-shard popcount serves every gauge: shards are equal
        # sized, so the global fill is exactly the mean of the per-shard
        # fills — no second O(m) reduction under the caller's op lock
        fills = self.shard_fill_ratios()
        fill = float(np.mean(fills))
        estimated = fill**self.config.k
        predicted = self.predicted_fpr()
        return {
            **base,
            "fill_ratio": fill,
            "bits_set": int(round(fill * self.config.m)),
            "estimated_fpr": estimated,
            "predicted_fpr": predicted,
            "fpr_drift": estimated - predicted,
            "fill_ratio_per_shard": fills,
        }

    @property
    def words_logical(self) -> np.ndarray:
        """Host copy in the logical per-shard layout: [shards, NBL, W]
        for blocked configs (undoing the fat per-shard view — same
        row-major bytes), else the device shape."""
        host = np.asarray(self.words)
        if self.config.block_bits:
            return host.reshape(
                self.config.shards,
                self.config.n_blocks_per_shard,
                self.config.words_per_block,
            )
        return host

    # Persistence: global layout = shard-major concatenation; bit
    # (s * m_local + p) of the export is bit p of shard s. Round-trips
    # through the same Redis-bitmap format as the single-device filter.

    def to_redis_bitmap(self) -> bytes:
        if self.config.block_bits or self.config.counting:
            raise ValueError(
                "blocked/counting layouts are not Redis-bitmap exportable "
                "(different position spec); use to_bytes"
            )
        host = np.asarray(self.words).reshape(-1)
        return words_to_redis_bitmap(host, self.config.m)

    @classmethod
    def from_redis_bitmap(
        cls, config: FilterConfig, data: bytes, **kwargs
    ) -> "ShardedBloomFilter":
        if config.block_bits or config.counting:
            raise ValueError("blocked/counting layouts restore via from_bytes")
        f = cls(config, **kwargs)
        words = redis_bitmap_to_words(data, config.m).reshape(
            config.shards, config.n_words_per_shard
        )
        f.words = jax.device_put(jnp.asarray(words), f.sharding)
        return f

    # blocked-layout persistence: raw LE words, shard-major then row-major

    def to_bytes(self) -> bytes:
        return np.asarray(self.words).reshape(-1).astype("<u4").tobytes()

    @classmethod
    def from_bytes(
        cls, config: FilterConfig, data: bytes, **kwargs
    ) -> "ShardedBloomFilter":
        f = cls(config, **kwargs)
        arr = np.frombuffer(data, dtype="<u4").astype(np.uint32)
        f.words = jax.device_put(
            jnp.asarray(arr.reshape(f.words.shape)), f.sharding
        )
        return f
