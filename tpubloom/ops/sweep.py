"""Pallas dense partition-sweep insert — the TPU hot-loop escape hatch.

Why this exists: XLA's scatter on TPU applies row updates ~serially
(~100ns/row measured on v5e), so the sorted-unique row scatter in
:func:`tpubloom.ops.blocked.blocked_insert` caps batched inserts at
~7M rows/sec regardless of bandwidth. This kernel replaces the scatter
with work the TPU is actually built for:

1. keys are sorted by owning block (``lax.sort`` — cheap, ~3ms/1M on
   v5e for 3 columns);
2. the block array is streamed HBM -> VMEM -> HBM **once per batch** in
   ``R``-row partitions (the Pallas grid pipeline double-buffers this
   stream automatically);
3. each partition's updates (a contiguous slice of the sorted key
   stream, located via precomputed partition boundaries and fetched
   with double-buffered manual DMA) are merged entirely in UPDATE space
   ([KMAX, *] — nothing here scales with R*block_bits) by **exact
   one-hot matmuls on the MXU**, then placed with one weight-1 term per
   touched row. See ``_kernel``'s chunk_delta for the stage list.

Exactness rules (every matmul runs as bf16 passes on the MXU):
operands are 0/1 one-hots, power-of-two weights, or values <= 255
(8-bit "quarter" splits of packed words) — all bf16-integer-exact —
with f32 accumulation. Packing/unpacking/transposing are themselves
matmuls against constant weight matrices because Mosaic supports
neither sublane<->lane reshapes, nor static lane slicing, nor sublane
shifts (the latter two MISCOMPILE silently — every workaround here was
validated against the XLA scatter path on real TPU).

Variants sharing the machinery:
* plain insert (``make_sweep_insert_fn`` / ``apply_blocked_updates``,
  also the per-device hot loop of the sharded filter);
* fused test-and-insert (``with_presence``): pre-batch membership is
  extracted from the old tile during the same pass and returned in
  original key order via a single-column unsort sort;
* blocked-counting update (``_count_kernel``): saturating 4-bit
  nibble add/subtract, no merge stage (counts are additive).

Measured on v5e at m=2^32, k=7, B=4M: 20.1M fused insert+query
keys/s vs 5.5M for the XLA sorted-scatter path — with bit-identical
results (same blocked position spec as :mod:`tpubloom.ops.blocked`;
the CPU oracle is the shared ground truth).

Adversarial skew (duplicate keys, tiny filters) is handled by an
in-kernel chunk loop: a partition with more than KMAX updates fetches
and merges ceil(n/KMAX) chunks serially. Batch-padding keys carry the
sentinel block id ``n_blocks`` and sort past every real partition.

Parity: reference hot path is SETBIT-per-position against the m-bit
array (BASELINE.json north_star); this is that hot loop, restructured
as sort + dense sweep because random-access SETBIT is precisely what
TPU HBM cannot do fast.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpubloom.ops import blocked

#: ``jax.named_scope`` names of the sweep programs' stages after the
#: hash (``tpubloom.ops.hashing.HASH_SCOPE``): sorting keys by owning block and building the
#: update stream; the partition-sweep kernel (or its fallback); and
#: putting presence verdicts back in key order. Each device op carries
#: its stage in the HLO ``op_name`` metadata.
SORT_SCOPE = "tpubloom/sort"
SWEEP_SCOPE = "tpubloom/sweep"
UNSORT_SCOPE = "tpubloom/unsort"


class InFlight:
    """Depth-1 host-side double buffer (ISSUE 10).

    The Pallas grid pipeline double-buffers the HBM stream *inside* one
    kernel; this is the same idea one level up, for the host feed: a
    batching driver (the server's ingestion coalescer, bench loops)
    launches batch N unfenced, parks ``(handle, payload)`` here, stages
    batch N+1's host_prep/H2D while N's kernel runs, and only then
    calls :meth:`take` — which fences N and hands back its payload for
    completion. JAX async dispatch does the actual overlap; this class
    just keeps the bookkeeping (and the fence) in one place.
    """

    def __init__(self):
        self._handle = None
        self._payload = None

    @property
    def pending(self) -> bool:
        return self._payload is not None

    def put(self, handle, payload):
        """Park one launched batch; returns the PREVIOUS batch's
        ``(payload, fence_error)`` pair fenced (``(None, None)`` when
        nothing was in flight) — see :meth:`take`."""
        prev = self.take()
        self._handle, self._payload = handle, payload
        return prev

    def take(self):
        """Fence and return ``(payload, fence_error)`` — both None when
        idle. The donated-buffer case is BENIGN and swallowed: with
        ``donate_argnums`` a later kernel on the same state consumes
        (deletes) this handle's buffer, and ``block_until_ready`` on a
        donated buffer raises instead of waiting — but the data
        dependency already guarantees this kernel completed before its
        consumer does. Any OTHER fence error (device OOM, a real kernel
        failure) is RETURNED, not raised or swallowed: the caller must
        fail the batch's waiters rather than ack work that never
        happened."""
        if self._payload is None:
            return None, None
        handle, payload = self._handle, self._payload
        self._handle = self._payload = None
        err = None
        if handle is not None and hasattr(handle, "block_until_ready"):
            try:
                handle.block_until_ready()
            except Exception as e:  # noqa: BLE001 — classified below
                msg = str(e).lower()
                if "donated" not in msg and "deleted" not in msg:
                    err = e
        return payload, err


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def choose_params(
    n_blocks: int, batch: int, *, R: int | None = None
) -> tuple[int, int]:
    """(R rows/partition, KMAX update-slots/fetch) for a filter/batch shape.

    Total MXU work scales with n_blocks*KMAX and per-partition overhead
    with n_blocks/R, so R balances the two (tuned on v5e); KMAX covers
    the Poisson(lambda = batch/P) occupancy out to ~8 sigma (the chunk
    loop correctness-covers anything beyond), is a multiple of 8 (DMA
    sublane tiling) and capped at 1024 — a VMEM bound only; exactness
    never depends on it (counts accumulate in f32, overflow goes to the
    chunk loop).
    """
    import math

    if R is None:
        # prefer per-partition occupancy (lambda) in ~[64, 256]: smaller
        # starves the MXU stages, larger inflates the KMAX^2 same-row
        # matmul (measured sweet spot on v5e)
        best = None
        for cand in (512, 1024):
            if cand > n_blocks or n_blocks % cand:
                continue
            lam = batch * cand // n_blocks
            score = abs(math.log2(max(lam, 1)) - 7)  # target lambda ~128
            if best is None or score < best[0]:
                best = (score, cand)
        R = best[1] if best else min(512, n_blocks)
    P = max(1, n_blocks // R)
    lam = max(1, batch // P)
    kmax = lam + max(16, int(8 * math.sqrt(lam)))
    kmax = min(1024, max(16, (kmax + 7) // 8 * 8))
    return R, kmax


def auto_insert_path(
    backend: str,
    n_blocks: int,
    batch: int,
    words_per_block: int = 16,
    *,
    presence: bool = False,
) -> str:
    """The implementation ``insert_path="auto"`` resolves to — the single
    source of truth shared by :func:`tpubloom.filter.make_blocked_insert_fn`
    and the benchmark's metadata. The Mosaic kernel only lowers on TPU;
    every other backend (cpu, gpu, ...) takes the XLA scatter path.
    ``presence`` must match the caller's fused-test-and-insert intent:
    the presence kernel has tighter caps, so the applicability decision
    and the kernel actually run must use the same predicate."""
    if backend == "tpu" and sweep_applicable(
        n_blocks, batch, words_per_block, presence=presence
    ):
        return "sweep"
    return "scatter"


def resolve_insert_path(
    config, batch: int, backend: str | None = None, *, presence: bool = False,
    n_blocks: int | None = None,
) -> str:
    """Resolve ``config.insert_path`` ("auto"/"sweep"/"scatter") for a
    batch size on the current (or given) backend. The ONE funnel for
    every insert-path decision (single-chip, presence, and — via the
    ``n_blocks`` override, which the sharded per-device hot loop uses to
    pass its LOCAL row count — the shard_map paths)."""
    if config.insert_path != "auto":
        return config.insert_path
    if backend is None:
        backend = jax.default_backend()
    return auto_insert_path(
        backend,
        config.n_blocks if n_blocks is None else n_blocks,
        batch,
        config.words_per_block,
        presence=presence,
    )


def sweep_applicable(
    n_blocks: int, batch: int, words_per_block: int = 16, *,
    presence: bool = False,
) -> bool:
    """The sweep wins when the array is large enough that partitions
    outnumber DMA latency and per-partition occupancy fits the fetch
    window; tiny filters / huge-batch-tiny-filter shapes stay on the
    sorted-scatter path."""
    if words_per_block + 2 > 128:
        # the update-stream row holds block id + W mask words + key idx
        # in 128 lanes; block_bits=4096 (W=128) does not fit
        return False
    if choose_fat_params(n_blocks, batch, words_per_block, presence=presence):
        return True
    R, kmax = choose_params(n_blocks, batch)
    P = max(1, n_blocks // R)
    if n_blocks % R != 0 or R % 32 != 0:
        return False
    if batch * R < 8 * n_blocks:
        # minimum per-partition occupancy (lambda >= 8): the sweep streams
        # the WHOLE block array HBM->VMEM->HBM per call, so a sparse batch
        # (e.g. a scalar insert into a 2^23-block filter) would pay the
        # full-array stream for a handful of rows — orders of magnitude
        # slower than the row scatter. Break-even on v5e is lambda ~1
        # (NB*128B / 819GB/s vs ~100ns/row scatter); 8 adds margin.
        return False
    # kmax covers lambda + 8 sigma by construction unless the 1024 cap
    # binds (tiny filter / huge batch), where the chunk loop would
    # serialize every partition
    return P >= 8 and batch // P < kmax


_ALIGN = 8  # Mosaic sublane tiling: DMA offsets/shapes on dim 0 in units of 8


def _kernel(
    starts_ref,  # SMEM [P+1] i32 (scalar prefetch)
    upd_ref,  # ANY [Btot, 128] u32: col 0 = block id, cols 1..W = mask words
    blocks_ref,  # VMEM [R, W] u32 (auto-streamed partition of the array)
    *rest,  # out_ref [, pres_ref], scratch sup_ref, sems
    R: int,
    KMAX: int,
    W: int,
    PRES: bool = False,
):
    if PRES:
        out_ref, pres_ref, sup_ref, sems = rest
    else:
        out_ref, sup_ref, sems = rest
        pres_ref = None
    p = pl.program_id(0)
    num_p = pl.num_programs(0)
    s0 = starts_ref[p]
    # DMA windows start at the 8-aligned floor of the partition start;
    # rows dragged in from the neighbour partition are inert (their
    # one-hot row match fails), so no count bookkeeping is needed.
    off0 = (s0 // _ALIGN) * _ALIGN
    end = starts_ref[p + 1]

    def fetch(slot, off):
        cp = pltpu.make_async_copy(
            upd_ref.at[pl.ds(off, KMAX), :], sup_ref.at[slot], sems.at[slot]
        )
        cp.start()
        return cp

    def wait(slot):
        pltpu.make_async_copy(
            upd_ref.at[pl.ds(0, KMAX), :], sup_ref.at[slot], sems.at[slot]
        ).wait()

    slot = lax.rem(p, 2)

    # chunk 0 of partition 0 has no predecessor to prefetch it
    @pl.when(p == 0)
    def _():
        fetch(0, off0)

    # prefetch chunk 0 of the NEXT partition into the other slot
    @pl.when(p + 1 < num_p)
    def _():
        fetch(1 - slot, (starts_ref[p + 1] // _ALIGN) * _ALIGN)

    wait(slot)

    col512 = lax.broadcasted_iota(jnp.int32, (KMAX, W * 32), 1)
    colsR = lax.broadcasted_iota(jnp.int32, (KMAX, R), 1)
    base = jnp.uint32(p * R)

    # pack weights: bit-plane column c = b*W + w contributes 2^(b mod 8)
    # to output column (b // 8) * W + w — the masks as 4W 8-bit
    # quarters. Quarter splitting keeps every packed value <= 255, which
    # is EXACT in bf16 — the MXU runs "f32" matmuls as bf16 passes, so
    # operands and results must stay in bf16's integer-exact range.
    ccol = lax.broadcasted_iota(jnp.int32, (W * 32, 4 * W), 0)
    hcol = lax.broadcasted_iota(jnp.int32, (W * 32, 4 * W), 1)
    b_of_c = ccol // W
    w_of_c = lax.rem(ccol, W)
    pack_w = jnp.where(
        (w_of_c + (b_of_c // 8) * W) == hcol,
        (1 << lax.rem(b_of_c, 8)).astype(jnp.float32),
        jnp.float32(0),
    ).astype(jnp.bfloat16)
    # combine weights: [4W, W] matrices folding quarter columns into
    # 16-bit half-words (q0 + 256*q1, and q2 + 256*q3) — both f32-exact
    # (<= 65535). Matmul-based because static lane slicing of the 4W
    # array miscompiles on Mosaic.
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

    def chunk_delta(slot, want_presence=False):
        """delta[R, W] u32 word-OR contribution of the update slice in
        `slot` (and, when asked, the pre-update membership of each slot).
        All heavy lifting happens in update space ([KMAX, *]); nothing
        here scales with R*W*32.

        MXU stages (all exact):
          same  = oh @ oh^T        0/1 same-row indicator   (bf16 x bf16)
          cnts  = same @ bits      per-slot merged bit counts
          lohi  = present @ pack_w merged masks as 16-bit halves, f32
          delta = sel_first^T @ lohi  one exact f32 row per touched block
        """
        buf = sup_ref[slot]  # [KMAX, 128] u32
        rl = (buf[:, 0:1] - base).astype(jnp.int32)  # [KMAX, 1]
        # one-hot row match; rows outside [0, R) (neighbour partitions,
        # sentinel tail) wrapped far out of range and match no column.
        # NB: selects stay in 32-bit lanes (f32) before converting to
        # bf16 — a 32-bit predicate selecting 16-bit values trips a
        # Mosaic relayout bug ("non-singleton dimension replicated").
        ohf = jnp.where(rl == colsR, jnp.float32(1), jnp.float32(0))
        oh = ohf.astype(jnp.bfloat16)  # [KMAX, R]
        m = buf[:, 1 : W + 1]  # [KMAX, W] mask words
        # bit-plane expansion, b-major layout: column c = b*W + w holds
        # bit b of word w -> replicate the W words 32x along lanes, then
        # shift each lane by c // W.
        rep = jnp.concatenate([m] * 32, axis=1)  # [KMAX, W*32]
        bits = (rep >> (col512 // W).astype(jnp.uint32)) & _u32(1)
        bitsf = bits.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
        # same-row indicator via the Kronecker split of the one-hot:
        # r = 32*hi + lo, so oh = oh_hi (x) oh_lo and
        # same = (oh_hi oh_hi^T) * (oh_lo oh_lo^T) elementwise — two
        # contractions of depth R/32 + 32 instead of one of depth R
        # (~10x less MXU work for the kernel's biggest matmul). Exact:
        # all operands 0/1; out-of-range rows miss the hi match.
        rl_hi = rl // 32
        rl_lo = rl - rl_hi * 32
        ohh = jnp.where(
            rl_hi == lax.broadcasted_iota(jnp.int32, (KMAX, R // 32), 1),
            jnp.float32(1), jnp.float32(0),
        ).astype(jnp.bfloat16)
        ohl = jnp.where(
            rl_lo == lax.broadcasted_iota(jnp.int32, (KMAX, 32), 1),
            jnp.float32(1), jnp.float32(0),
        ).astype(jnp.bfloat16)
        same_hi = lax.dot_general(
            ohh, ohh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        same_lo = lax.dot_general(
            ohl, ohl, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        same = (same_hi * same_lo).astype(jnp.bfloat16)  # [KMAX, KMAX]
        cnts = lax.dot_general(
            same, bitsf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [KMAX, W*32] per-slot group-merged bit counts
        present = jnp.where(cnts > 0, jnp.float32(1), jnp.float32(0)).astype(
            jnp.bfloat16
        )
        # select exactly one representative slot per row group: slot j
        # is "first" iff no earlier slot j' < j shares its row. Derived
        # from `same` with an iota mask (no sublane shifts — those
        # miscompile on Mosaic).
        jj = lax.broadcasted_iota(jnp.int32, (KMAX, KMAX), 0)
        kk = lax.broadcasted_iota(jnp.int32, (KMAX, KMAX), 1)
        earlier = jnp.where(kk < jj, same.astype(jnp.float32), jnp.float32(0))
        n_before = jnp.sum(earlier, axis=1, keepdims=True)  # [KMAX, 1]
        first = jnp.where(n_before == 0, jnp.float32(1), jnp.float32(0))
        ohsel = (ohf * first).astype(jnp.bfloat16)  # one 1 per touched row
        quarters = lax.dot_general(
            present, pack_w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [KMAX, 4W] merged masks as 8-bit quarters (bf16-exact)
        delta_q = lax.dot_general(
            ohsel, quarters.astype(jnp.bfloat16), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.bfloat16)  # [R, 4W] — exact: one weight-1 term per row
        lo = lax.dot_general(
            delta_q, comb_lo, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [R, W] f32-exact 16-bit lo halves
        hi = lax.dot_general(
            delta_q, comb_hi, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        delta = lo.astype(jnp.int32).astype(jnp.uint32) | (
            hi.astype(jnp.int32).astype(jnp.uint32) << _u32(16)
        )
        if not want_presence:
            return delta

        # -- pre-update membership of each slot (test-and-insert) ------
        # Extract each slot's OLD block row with the same one-hot matmul,
        # one 8-bit quarter at a time (bf16-exact <= 255), and test
        # (row & mask) == mask across all W words and 4 quarters.
        tile = blocks_ref[:]  # [R, W] u32, pre-update by construction
        acc_ok = None
        for q in range(4):
            tq_f = (
                ((tile >> _u32(8 * q)) & _u32(0xFF))
                .astype(jnp.int32)
                .astype(jnp.float32)
                .astype(jnp.bfloat16)
            )
            rq = lax.dot_general(
                oh, tq_f, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [KMAX, W] f32-exact quarter of the slot's old row
            rq_u = rq.astype(jnp.int32).astype(jnp.uint32)
            mq = (m >> _u32(8 * q)) & _u32(0xFF)
            ok = jnp.where((mq & rq_u) == mq, jnp.float32(1), jnp.float32(0))
            acc_ok = ok if acc_ok is None else acc_ok * ok
        # all W words must match; slots with no row in this partition
        # (oh all-zero -> row 0) produce garbage, masked by `real` below
        hit = jnp.min(acc_ok, axis=1, keepdims=True)  # [KMAX, 1] f32
        return delta, hit

    delta, hit0 = chunk_delta(slot, want_presence=True) if PRES else (
        chunk_delta(slot), None
    )

    # overflow chunks (adversarial skew only): serial fetch + word-OR.
    # Groups spanning a chunk boundary contribute one partial merge per
    # chunk; OR-accumulating packed words keeps that exact. (Presence is
    # emitted for chunk-0 windows only; the host falls back to a gather
    # query for batches where any partition overflows.)
    nch = (end - off0 + (KMAX - 1)) // KMAX

    def body(c, acc):
        fetch(slot, off0 + c * KMAX).wait()
        return acc | chunk_delta(slot)

    delta = lax.fori_loop(1, nch, body, delta)

    if PRES:
        # Pack (idx+1 | hit<<31) per slot into an [8, KMAX/8] tile, slot
        # j at (j % 8, j // 8). The sublane->lane move is done with four
        # exact byte matmuls ((oh_a * v_byte)^T @ oh_b) because Mosaic
        # supports neither the reshape nor sublane shifts.
        buf = sup_ref[slot]
        idxp1 = buf[:, W + 1 : W + 2]  # [KMAX, 1] u32, idx+1 (0 = filler)
        ipos = lax.broadcasted_iota(jnp.int32, (KMAX, 1), 0) + off0
        real = (ipos >= s0) & (ipos < end) & (idxp1 > 0)
        hbit = jnp.where(hit0 > 0.5, _u32(0x80000000), _u32(0))
        v = jnp.where(real, idxp1 | hbit, _u32(0))  # [KMAX, 1]
        jj8 = lax.broadcasted_iota(jnp.int32, (KMAX, 8), 0)
        aa8 = lax.broadcasted_iota(jnp.int32, (KMAX, 8), 1)
        oh_a = jnp.where(jj8 % 8 == aa8, jnp.float32(1), jnp.float32(0))
        jjc = lax.broadcasted_iota(jnp.int32, (KMAX, KMAX // 8), 0)
        ccc = lax.broadcasted_iota(jnp.int32, (KMAX, KMAX // 8), 1)
        oh_b = jnp.where(jjc // 8 == ccc, jnp.float32(1), jnp.float32(0)).astype(
            jnp.bfloat16
        )
        pres = jnp.zeros((8, KMAX // 8), jnp.uint32)
        for q in range(4):
            vb = (
                ((v >> _u32(8 * q)) & _u32(0xFF))
                .astype(jnp.int32)
                .astype(jnp.float32)
            )
            left = (oh_a * vb).astype(jnp.bfloat16)  # [KMAX, 8]
            outq = lax.dot_general(
                left, oh_b, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [8, KMAX//8] f32-exact bytes
            pres = pres | (
                outq.astype(jnp.int32).astype(jnp.uint32) << _u32(8 * q)
            )
        pres_ref[:] = pres

    out_ref[:] = blocks_ref[:] | delta


def sweep_insert(
    blocks: jnp.ndarray,
    updates: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    R: int,
    KMAX: int,
    interpret: bool = False,
    with_presence: bool = False,
):
    """Apply sorted (block, mask) updates to ``blocks`` via the sweep kernel.

    Args:
      blocks: ``uint32[NB, W]``.
      updates: ``uint32[Btot, 128]`` sorted update stream: column 0 is the
        block id (ascending; padding/sentinel rows hold ``NB`` and sit at
        the tail), columns ``1..W`` the mask words, column ``W+1`` the
        original key index + 1 when ``with_presence`` (0 = filler), the
        rest zero. The 128-lane row keeps every DMA slice tile-aligned.
        ``Btot`` must include ``>= KMAX + 8`` rows of tail padding so
        chunk DMA windows stay in bounds.
      starts: ``int32[P+1]`` partition boundaries
        (``starts[p]`` = first index with ``block id >= p*R``).

    Returns ``new_blocks``, or ``(new_blocks, pres)`` when
    ``with_presence``: ``pres`` is ``uint32[P*8, KMAX//8]`` holding
    ``idx+1 | was_present << 31`` per update slot (slot j of partition p
    at ``[p*8 + j % 8, j // 8]``; 0 = no slot). Presence is relative to
    the PRE-batch array and only valid when no partition overflowed its
    chunk-0 window (callers check and fall back).
    """
    NB, W = blocks.shape
    P = NB // R
    out_shape = jax.ShapeDtypeStruct((NB, W), jnp.uint32)
    out_spec = pl.BlockSpec((R, W), lambda p, *_: (p, 0))
    if with_presence:
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct((P * 8, KMAX // 8), jnp.uint32),
        )
        out_spec = (out_spec, pl.BlockSpec((8, KMAX // 8), lambda p, *_: (p, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((R, W), lambda p, *_: (p, 0)),
        ],
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((2, KMAX, 128), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, R=R, KMAX=KMAX, W=W, PRES=with_presence),
        out_shape=out_shape,
        grid_spec=grid_spec,
        input_output_aliases={2: 0},
        interpret=interpret,
    )
    return fn(starts, updates, blocks)


def _stream_scaffold(bs, nb: int, P: int, R: int, KMAX: int):
    """Shared host-side sweep-stream assembly: partition boundaries from
    the sorted block ids, plus the padded 128-lane update buffer with
    column 0 = block id (sentinel ``nb`` rows in the tail slack so every
    8-aligned chunk DMA window stays in bounds). Callers fill their
    payload columns into the returned buffer."""
    B = bs.shape[0]
    starts = jnp.searchsorted(
        bs, (jnp.arange(P + 1, dtype=jnp.int32) * R).astype(jnp.int32)
    ).astype(jnp.int32)
    pad = KMAX + _ALIGN
    upd = jnp.zeros((B + pad, 128), jnp.uint32)
    upd = upd.at[:, 0].set(
        jnp.concatenate([bs.astype(jnp.uint32), jnp.full((pad,), nb, jnp.uint32)])
    )
    return starts, upd


def _count_kernel(
    starts_ref,  # SMEM [P+1] i32 (scalar prefetch)
    upd_ref,  # ANY [Btot, 128] u32: col 0 = block id, cols 1..W = nibble counts
    blocks_ref,  # VMEM [R, W] u32 (auto-streamed partition of the counters)
    out_ref,  # VMEM [R, W] u32
    sup_ref,  # VMEM scratch [2, KMAX, 128] u32
    sems,  # DMA sems [2]
    *,
    R: int,
    KMAX: int,
    W: int,
    INCREMENT: bool,
):
    """Blocked-counting partition sweep: saturating nibble add/subtract.

    Per update slot the stream carries the key's per-counter multiplicity
    pre-packed as 4-bit nibbles in W words — the SAME (word, nibble)
    layout as the counter storage itself, so one concat-and-shift
    unpacks either side. Counts are additive, so no same-row merge or
    representative selection is needed: counts[R, 128 planes] is one
    exact one-hot matmul, accumulated over overflow chunks (clamped at
    16 per chunk — already saturating/flooring, and it keeps every f32
    sum exact under adversarial duplicate skew). The tile is fully
    rewritten with min(15, old + cnt) (insert) / max(0, old - cnt)
    (delete) — identical one-clamp semantics to ops.counting
    (cpu_ref._counter_add ground truth).
    """
    p = pl.program_id(0)
    num_p = pl.num_programs(0)
    s0 = starts_ref[p]
    off0 = (s0 // _ALIGN) * _ALIGN
    end = starts_ref[p + 1]

    def fetch(slot, off):
        cp = pltpu.make_async_copy(
            upd_ref.at[pl.ds(off, KMAX), :], sup_ref.at[slot], sems.at[slot]
        )
        cp.start()
        return cp

    def wait(slot):
        pltpu.make_async_copy(
            upd_ref.at[pl.ds(0, KMAX), :], sup_ref.at[slot], sems.at[slot]
        ).wait()

    slot = lax.rem(p, 2)

    @pl.when(p == 0)
    def _():
        fetch(0, off0)

    @pl.when(p + 1 < num_p)
    def _():
        fetch(1 - slot, (starts_ref[p + 1] // _ALIGN) * _ALIGN)

    wait(slot)

    CPB = W * 8  # counters per block = nibble planes
    colC = lax.broadcasted_iota(jnp.int32, (KMAX, CPB), 1)
    colsR = lax.broadcasted_iota(jnp.int32, (KMAX, R), 1)
    base = jnp.uint32(p * R)

    def chunk_counts(slot):
        """Clamped per-(row, plane) multiplicities from the slot buffers."""
        buf = sup_ref[slot]  # [KMAX, 128] u32
        rl = (buf[:, 0:1] - base).astype(jnp.int32)
        ohf = jnp.where(rl == colsR, jnp.float32(1), jnp.float32(0))
        oh = ohf.astype(jnp.bfloat16)  # [KMAX, R]
        m = buf[:, 1 : W + 1]  # [KMAX, W] packed 4-bit multiplicities
        # plane c = (nibble c // W) of word (c mod W) — concat W-wide
        # copies, shift each lane by 4 * (c // W)
        rep = jnp.concatenate([m] * 8, axis=1)  # [KMAX, CPB]
        nib = (rep >> ((colC // W).astype(jnp.uint32) * _u32(4))) & _u32(15)
        nibf = nib.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
        cnts = lax.dot_general(
            oh, nibf, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [R, CPB], exact (<= 15 * KMAX < 2^24)
        return jnp.minimum(cnts, jnp.float32(16))

    acc = chunk_counts(slot)
    nch = (end - off0 + (KMAX - 1)) // KMAX

    def body(c, a):
        fetch(slot, off0 + c * KMAX).wait()
        return a + chunk_counts(slot)

    acc = lax.fori_loop(1, nch, body, acc)

    # old counters, same plane layout
    tile = blocks_ref[:]
    trep = jnp.concatenate([tile] * 8, axis=1)  # [R, CPB]
    tcolC = lax.broadcasted_iota(jnp.int32, (R, CPB), 1)
    old = (trep >> ((tcolC // W).astype(jnp.uint32) * _u32(4))) & _u32(15)
    oldf = old.astype(jnp.int32).astype(jnp.float32)
    if INCREMENT:
        new = jnp.minimum(oldf + acc, jnp.float32(15))
    else:
        new = jnp.maximum(oldf - acc, jnp.float32(0))
    newb = new.astype(jnp.bfloat16)  # <= 15, bf16-exact

    # pack planes back into words: byte q of word w = plane(2q, w) +
    # 16 * plane(2q+1, w); four separate matmuls (no lane slicing)
    pc = lax.broadcasted_iota(jnp.int32, (CPB, W), 0)
    pw = lax.broadcasted_iota(jnp.int32, (CPB, W), 1)
    n_of = pc // W
    w_of = lax.rem(pc, W)
    packed = jnp.zeros((R, W), jnp.uint32)
    for q in range(4):
        wq = jnp.where(
            (w_of == pw) & (n_of // 2 == q),
            jnp.where(lax.rem(n_of, 2) == 0, jnp.float32(1), jnp.float32(16)),
            jnp.float32(0),
        ).astype(jnp.bfloat16)
        byte = lax.dot_general(
            newb, wq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [R, W] f32-exact bytes
        packed = packed | (
            byte.astype(jnp.int32).astype(jnp.uint32) << _u32(8 * q)
        )
    out_ref[:] = packed


def sweep_counter_update(
    blocks: jnp.ndarray,
    updates: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    R: int,
    KMAX: int,
    increment: bool,
    interpret: bool = False,
) -> jnp.ndarray:
    """Apply sorted per-block nibble-count updates to the packed counters."""
    NB, W = blocks.shape
    P = NB // R
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((R, W), lambda p, *_: (p, 0)),
        ],
        out_specs=pl.BlockSpec((R, W), lambda p, *_: (p, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, KMAX, 128), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(
            _count_kernel, R=R, KMAX=KMAX, W=W, INCREMENT=increment
        ),
        out_shape=jax.ShapeDtypeStruct((NB, W), jnp.uint32),
        grid_spec=grid_spec,
        input_output_aliases={2: 0},
        interpret=interpret,
    )
    return fn(starts, updates, blocks)


def apply_counter_updates(
    blocks: jnp.ndarray,
    blk: jnp.ndarray,
    cpos: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    counters_per_block: int,
    k: int,
    increment: bool,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Apply each valid key's blocked-counting update to ``blocks`` via the
    counting sweep (saturating +1 / flooring -1 per counter occurrence).

    The kernel-facing entry point shared by the single-chip path and the
    sharded per-device path (which routes keys first and passes
    device-local row ids). ``blk int32[B]`` block rows, ``cpos
    uint32[B, k]`` in-block counter positions, ``valid bool[B]``; invalid
    keys are dropped. Requires ``k <= 15`` (per-key multiplicity must fit
    the 4-bit stream nibbles).
    """
    nb, w = blocks.shape
    B = blk.shape[0]
    cpb = counters_per_block
    R, KMAX = choose_params(nb, B)
    if nb % R != 0 or w + 1 > 128:
        raise ValueError(
            f"sweep counter update does not support this shape "
            f"(n_blocks={nb}, R={R}, words_per_block={w})"
        )
    P = nb // R
    interp = jax.default_backend() == "cpu" if interpret is None else interpret
    blk = jnp.where(valid, blk, nb)
    cols, nbits, packed = _pack_positions(cpos, cpb, k)
    sorted_cols = lax.sort((blk,) + cols, num_keys=1)
    bs = sorted_cols[0]
    cpos_s = _unpack_positions(sorted_cols[1:], cpb, k, nbits, packed)
    # per-key multiplicity of each counter, packed 4 bits per nibble
    # in the counter-storage (word, nibble) layout: counter c lives
    # in word c >> 3, nibble c & 7 — multiplicity <= k <= 15
    planes = jnp.zeros((B, cpb), jnp.uint32)
    iota_c = lax.broadcasted_iota(jnp.uint32, (B, cpb), 1)
    for i in range(k):
        planes = planes + (cpos_s[:, i : i + 1] == iota_c).astype(jnp.uint32)
    pw = planes.reshape(B, w, 8)
    shifts = (jnp.arange(8, dtype=jnp.uint32) * 4)[None, None, :]
    cnt_words = jnp.sum(pw << shifts, axis=2, dtype=jnp.uint32)  # [B, W]
    starts, upd = _stream_scaffold(bs, nb, P, R, KMAX)
    upd = upd.at[:B, 1 : w + 1].set(cnt_words)
    return sweep_counter_update(
        blocks, upd, starts,
        R=R, KMAX=KMAX, increment=increment, interpret=interp,
    )


def make_sweep_counter_fn(
    config, *, increment: bool, interpret: bool | None = None,
    storage_fat: bool = False,
):
    """Pure ``(blocks[NB,W], keys_u8, lengths) -> blocks`` blocked-counting
    update (insert = saturating +1 per counter occurrence, delete =
    flooring -1) via the partition sweep. Bit-identical to the flat
    counting kernel applied at positions ``blk * counters_per_block + c``
    (tpubloom.filter.make_blocked_counter_fn's fallback path).

    Prefers the fat-row counting kernel when the shape qualifies (the
    128-lane DMA tier — benchmarks/RESULTS_r3.md §2); the legacy
    [NB, W]-tile kernel is the fallback. ``storage_fat``: blocks are the
    fat [NB/J, 128] view in and out.
    """
    nb, cpb, w = config.n_blocks, config.counters_per_block, config.words_per_block
    k, seed, bh = config.k, config.seed, config.block_hash

    def update(blocks, keys_u8, lengths):
        valid = lengths >= 0
        blk, cpos = blocked.block_positions(
            keys_u8, jnp.maximum(lengths, 0),
            n_blocks=nb, block_bits=cpb, k=k, seed=seed, block_hash=bh,
        )
        fat = choose_fat_params(nb, keys_u8.shape[0], w, counting=True)
        if fat is not None:
            return apply_fat_counter_updates(
                blocks, blk, cpos, valid,
                counters_per_block=cpb, k=k, increment=increment,
                params=fat, interpret=interpret, storage_fat=storage_fat,
            )
        out = apply_counter_updates(
            blocks.reshape(nb, w) if storage_fat else blocks,
            blk, cpos, valid,
            counters_per_block=cpb, k=k, increment=increment,
            interpret=interpret,
        )
        return out.reshape(blocks.shape) if storage_fat else out

    return update


def _pack_positions(bit: jnp.ndarray, block_bits: int, k: int):
    """Pack ``uint32[B, k]`` in-block positions into few u32 payload columns
    for the sort (9 bits each at block_bits=512). Returns
    ``(cols, nbits, packed)``; when ``k*log2(bb) > 64`` the positions ride
    the sort as one column each (``packed=False``). The explicit flag —
    not ``len(cols)`` — tells unpack which form it got (k=2 would be
    ambiguous otherwise)."""
    nbits = max(1, (block_bits - 1).bit_length())
    if k * nbits <= 64:
        lo = jnp.zeros(bit.shape[:-1], jnp.uint32)
        hi = jnp.zeros(bit.shape[:-1], jnp.uint32)
        for i in range(k):
            sh = i * nbits
            if sh < 32:
                lo = lo | (bit[..., i] << _u32(sh))
                if sh + nbits > 32:
                    hi = hi | (bit[..., i] >> _u32(32 - sh))
            else:
                hi = hi | (bit[..., i] << _u32(sh - 32))
        return (lo, hi), nbits, True
    return tuple(bit[..., i] for i in range(k)), nbits, False


def _unpack_positions(cols, block_bits: int, k: int, nbits: int, packed: bool):
    if not packed:
        return jnp.stack(cols, axis=-1)
    lo, hi = cols
    mask = _u32(block_bits - 1)
    outs = []
    for i in range(k):
        sh = i * nbits
        if sh < 32:
            v = lo >> _u32(sh)
            if sh + nbits > 32:
                v = v | (hi << _u32(32 - sh))
        else:
            v = hi >> _u32(sh - 32)
        outs.append(v & mask)
    return jnp.stack(outs, axis=-1)


def apply_blocked_updates(
    blocks: jnp.ndarray,
    blk: jnp.ndarray,
    bit: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    block_bits: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """OR each valid key's blocked-spec bits into ``blocks`` via the sweep.

    The kernel-facing entry point shared by the single-chip path and the
    sharded per-device path (which routes keys first and passes
    device-local row ids). ``blk int32[B]``, ``bit uint32[B, k]``
    (in-block positions), ``valid bool[B]``; invalid keys are dropped.
    """
    nb, w = blocks.shape
    B = blk.shape[0]
    k = bit.shape[-1]
    fat = choose_fat_params(nb, B, w)
    if fat is not None:
        return apply_fat_updates(
            blocks, blk, bit, valid,
            block_bits=block_bits, params=fat, interpret=interpret,
        )
    R, KMAX = choose_params(nb, B)
    if nb % R != 0 or w + 2 > 128 or R % 32 != 0:
        # R must be a multiple of 32 for the Kronecker one-hot split
        # (rows beyond 32*(R//32) would silently drop their inserts)
        raise ValueError(
            f"sweep insert does not support this shape (n_blocks={nb}, "
            f"R={R}, words_per_block={w}) — use insert_path='scatter'"
        )
    P = nb // R
    interp = jax.default_backend() == "cpu" if interpret is None else interpret
    with jax.named_scope(SORT_SCOPE):
        blk = jnp.where(valid, blk, nb)
        cols, nbits, packed = _pack_positions(bit, block_bits, k)
        sorted_cols = lax.sort((blk,) + cols, num_keys=1)
        bs = sorted_cols[0]
        bit_sorted = _unpack_positions(
            sorted_cols[1:], block_bits, k, nbits, packed
        )
        masks = blocked.build_masks(bit_sorted, w)
        starts, upd = _stream_scaffold(bs, nb, P, R, KMAX)
        upd = upd.at[:B, 1 : w + 1].set(masks)
    with jax.named_scope(SWEEP_SCOPE):
        return sweep_insert(
            blocks, upd, starts, R=R, KMAX=KMAX, interpret=interp
        )


# =========================================================================
# Fat-row (128-lane) partition sweep — "sweep3", the shipping TPU hot loop
# =========================================================================
#
# Why a second kernel generation: benchmarks/hbm_probe.py measured that
# this chip's Pallas DMA moves [*, W=16]-lane tiles at ~35 GB/s but
# [*, 128]-lane tiles at ~150-190 GB/s (the (8, 128) DMA tiling wastes
# 8x on narrow tiles), so the original per-block-row pipeline above was
# bandwidth-crippled by its own layout. A [NB, W] u32 block array is the
# SAME row-major memory as [NB/J, 128] with J = 128/W blocks per fat
# row, so the fat sweep:
#
# * sorts keys by skey = (blk mod J) * NBJ + (blk div J): J substreams,
#   one per block-column j; substream j's updates touch only lanes
#   [j*W, (j+1)*W) of the fat rows, so each substream's delta is
#   produced independently and lane-concatenated — no sublane<->lane
#   moves anywhere;
# * runs the placement one-hot over FAT rows (R8 per sub-tile), so the
#   cnt matmul is J-times narrower per window at equal coverage — the
#   int8 MXU does NB*bb*KJ MACs/pass with KJ ~ lambda+8sigma per
#   (j, window);
# * computes fused test-and-insert presence with ONE extra int8 matmul
#   per window (G = mask_bits @ oldrow_bits^T; slot hits iff
#   G[s, row(s)] == popcount(mask_s)) instead of per-slot extraction.
#
# Measured on the same chip / same stream (B=4M, m=2^32, k=7, bb=512,
# to-value timing): insert-only 31-34 ms (124-135M keys/s) vs 77 ms for
# the legacy kernel; fused test-and-insert 70 ms (60M keys/s) vs 115 ms.
# Results are bit-identical to the legacy kernel and the XLA scatter
# path (same blocked position spec).


# Device generations whose fat-kernel caps below are hardware-measured
# (benchmarks/out/presence_geom_r5.json, adversarial_r5.json,
# geom8m_r5.json). On any OTHER TPU generation every geometry is
# probe-compiled; on v5e itself, presence/counting geometries OUTSIDE
# the validated set below are probed too — round 5 measured that
# Mosaic's scoped-VMEM acceptance is NOT a clean function of the
# (bodies, volume) caps ((256,2,KJP=176) fails at 2.88M "volume" while
# (512,2,KJP=96) passes at 3.15M), so the caps prune the search and
# the probe is the ground truth for unlisted corners. A failed probe
# demotes to the next candidate shape / scatter path instead of
# erroring at first use.
_VALIDATED_DEVICE_KINDS = ("TPU v5 lite",)
_GEOM_PROBE_CACHE: dict = {}
#: per-device-kind PERSISTENT probe results (ISSUE 11 satellite, ADVICE
#: r5 #4): a cold start on an unvalidated TPU generation used to pay
#: ~60 s of speculative Mosaic compiles — and every rolling restart of
#: a fleet pays it again. Successful probes are written through to
#: ``$TPUBLOOM_CACHE_DIR`` (default ``<checkout>/.jax_cache/geomprobe``,
#: beside the compile cache), keyed by device kind, so the second
#: process start performs ZERO speculative probe compiles. Only
#: ``ok=True`` results persist: a failure demotes this process alone
#: and is counted (``geometry_probe_demotions``).
_GEOM_DISK_CACHE: dict = {}  # device kind -> set of ok key strings
_GEOM_DISK_LOADED: set = set()  # device kinds whose file was read
# (J, R8, S, KJP) tuples that compiled AND ran bit-exact on v5e
# hardware this round (adversarial_r5.json, presence_geom_r5.json,
# kj_slack_r5.json, geom8m_r5.json, bench/b_sweep runs).
_VALIDATED_GEOMS = {
    "presence": {
        (8, 512, 2, 96),    # B=4M shipping (KJ=352)
        (8, 512, 2, 104),   # B=4M/8M at 8-sigma (KJ=384)
        (8, 256, 2, 96),    # B=8M 6-sigma (KJ=352)
        (8, 256, 2, 104),   # B=8M 8-sigma (KJ=384)
        (8, 512, 1, 176),   # B=8M lambda=512 (KJ=648)
        (8, 1024, 1, 64),   # B=1M lambda=128 at R8=1024 (KJ=200)
        (8, 256, 4, 64),    # presence_geom (KJ=224)
        (8, 128, 4, 96),    # m=2^28 adversarial (KJ=352)
        (8, 128, 4, 64),    # small-filter corners (KJ<=224)
        (16, 512, 1, 64),   # bb=256 adversarial (KJ=200)
        (4, 256, 4, 352),   # bb=1024 pack=1 adversarial (KJ=352)
    },
    "counting": {
        (8, 256, 4, 64),    # config-4 B=4M (KJ=224)
        (8, 128, 4, 64),    # B=8M lambda=128 (73.2M ops/s)
        (8, 256, 2, 104),   # B=8M lambda=256 (74.0M — geom_ins_r5.json)
    },
}


def _probe_env():
    """Device kind when probe compiles apply (TPU backend), else None.
    The one seam between the probe machinery and the hardware — tests
    monkeypatch it to exercise the cache off-TPU. A backend that fails
    to initialise raises here: reading that as "not a TPU" would skip
    the probes and hide the device."""
    if jax.default_backend() != "tpu":
        return None
    return jax.devices()[0].device_kind


def _geom_cache_path(kind: str) -> str:
    import os
    import re

    from tpubloom.utils import compile_cache

    base = os.environ.get("TPUBLOOM_CACHE_DIR") or os.path.join(
        compile_cache.CHECKOUT_CACHE_DIR, "geomprobe"
    )
    slug = re.sub(r"[^A-Za-z0-9._-]+", "-", kind)
    return os.path.join(base, f"geomprobe-{slug}.json")


def _geom_cache_salt() -> str:
    """Version salt invalidating the persisted probe results: a stale
    ok=True surviving a kernel-code or jax/Mosaic upgrade would skip
    the probe for a geometry that no longer compiles — converting
    graceful demotion into a hard runtime failure at first real use.
    Upgrades cost one re-probe pass instead."""
    from tpubloom import version

    return f"{version.__version__}|jax-{jax.__version__}"


def _geom_disk_get(kind: str, key_str: str) -> bool:
    """True when a previous PROCESS probed this geometry ok on this
    device kind AT THIS CODE VERSION (best-effort: any read problem —
    missing file, torn JSON, CRC mismatch, salt mismatch — reads as a
    miss)."""
    if kind not in _GEOM_DISK_LOADED:
        _GEOM_DISK_LOADED.add(kind)
        from tpubloom.utils import crcjson

        payload = crcjson.load(_geom_cache_path(kind), ("geoms", "salt"))
        geoms = payload.get("geoms") if payload else None
        if payload is None or payload.get("salt") != _geom_cache_salt():
            geoms = None
        _GEOM_DISK_CACHE[kind] = set(
            geoms if isinstance(geoms, list) else ()
        )
    return key_str in _GEOM_DISK_CACHE.get(kind, ())


def _geom_disk_put(kind: str, key_str: str) -> None:
    """Write-through one ok probe result. Multi-process safe for the
    fleet-rolling-restart case the cache exists for: the file is
    RE-READ and unioned before each write (a sibling process's probes
    landed between our load and now must not be clobbered), and the
    write goes through a pid-unique path + ``os.replace`` so two
    concurrent writers cannot tear each other's tmp file. Best-effort
    throughout — a read-only cache dir must not break the hot path."""
    import os

    from tpubloom.utils import crcjson

    _GEOM_DISK_CACHE.setdefault(kind, set()).add(key_str)
    path = _geom_cache_path(kind)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        merged = set(_GEOM_DISK_CACHE[kind])
        current = crcjson.load(path, ("geoms", "salt"))
        if current and current.get("salt") == _geom_cache_salt():
            geoms = current.get("geoms")
            if isinstance(geoms, list):
                merged.update(geoms)
        _GEOM_DISK_CACHE[kind] = merged
        mine = f"{path}.{os.getpid()}"
        crcjson.store(mine, {
            "geoms": sorted(merged),
            "salt": _geom_cache_salt(),
        })
        os.replace(mine, path)
    except OSError:
        pass


def _probe_compile(fn, blocks_sds, upd_sds, starts_sds):
    """One speculative Mosaic AOT compile (counted in
    ``geometry_probe_compiles``). Compiles are local, so a failure is a
    real limit of the compiler for this shape. Returns ``(ok, exc)``."""
    from tpubloom.obs import counters as obs_counters

    obs_counters.incr("geometry_probe_compiles")
    try:
        jax.jit(fn).lower(blocks_sds, upd_sds, starts_sds).compile()
    except Exception as e:  # noqa: BLE001 — any compile failure demotes
        return False, e
    return True, None


_VALIDATED_KBJP_CAPS: dict = {}


def _validated_kbjp_cap(kind_name: str, sig) -> int:
    """Largest packed big-fetch row count (kbjp) any chooser-reachable
    lambda can pair with this validated (J, R8, S, KJP) signature —
    ADVICE r5 #3: the window-fetch scratch ``2*J*kbjp*128*4`` is part
    of the hardware-validated footprint, so a geometry whose kbjp
    exceeds what the signature pins must probe instead of riding the
    fast path. Derived by inverting the chooser's KJ(lambda) step
    function (slack 6 for presence, 8 otherwise) over the feasible
    lambda range; memoized — ~2k-iteration integer scan, once per
    signature per process."""
    cached = _VALIDATED_KBJP_CAPS.get((kind_name, sig))
    if cached is not None:
        return cached
    import math

    J, R8, S, KJP = sig
    w = 128 // J
    presence = kind_name == "presence"
    pk = fat_pack(w, presence)
    slack = 6 if presence else 8
    cap = 0
    for lam in range(8, 2049):
        kj = max(16, (lam + max(16, int(slack * math.sqrt(lam))) + 7) // 8 * 8)
        if kj > 1024 or _packed_rows(kj, pk) != KJP:
            continue
        kbj = ((lam * S + kj + 64 + 7) // 8) * 8
        cap = max(cap, _packed_rows(kbj, pk))
    _VALIDATED_KBJP_CAPS[(kind_name, sig)] = cap
    return cap


def fat_kernel_shapes(
    nb: int, w: int, geom, *, presence: bool = False, counting: bool = False,
    query: bool = False, batch: int | None = None,
):
    """``(kernel, (blocks, upd, starts) ShapeDtypeStructs)`` for the fat
    kernel the runtime launches at ``geom`` — what the geometry probe
    compiles and what ``tests/test_tpu_compile.py`` compiles for a
    described chip. With ``batch`` the update buffer carries the REAL
    runtime row count (ADVICE r5 #1: the compile is then shape-identical
    to the first real call)."""
    J, R8, S, KJ, KBJ = geom
    # pack must match the kernel the runtime will launch: both the
    # chooser's volume bound and apply_fat_counter_updates use
    # fat_pack(w, presence) — probing a pack=1 counting kernel would
    # validate a strictly lighter scoped-VMEM footprint than the real
    # PACK=4 unroll. The query kernel's stream carries the idx column
    # like presence streams, so its pack matches presence's.
    pk = fat_pack(w, presence or query)
    kbjp = _packed_rows(KBJ, pk)
    # update-stream rows exactly as _fat_stream will build them at
    # runtime; probes with no batch at hand keep the legacy stand-in
    if batch is None:
        upd_rows = kbjp + 16
    elif pk == 1:
        upd_rows = int(batch) + KBJ + _ALIGN
    else:
        upd_rows = -(-int(batch) // pk) + kbjp + _ALIGN
    NBJ = nb // J
    blocks_sds = jax.ShapeDtypeStruct((NBJ, 128), jnp.uint32)
    upd_sds = jax.ShapeDtypeStruct((upd_rows, 128), jnp.uint32)
    starts_sds = jax.ShapeDtypeStruct((J * (NBJ // R8) + 1,), jnp.int32)
    if counting:
        fn = functools.partial(
            fat_sweep_counter, J=J, R8=R8, S=S, KJ=KJ, KBJ=KBJ, W=w,
            increment=True, pack=pk,
        )
    elif query:
        fn = functools.partial(
            fat_sweep_query, J=J, R8=R8, S=S, KJ=KJ, KBJ=KBJ, W=w, pack=pk,
        )
    else:
        fn = functools.partial(
            fat_sweep_insert, J=J, R8=R8, S=S, KJ=KJ, KBJ=KBJ, W=w,
            with_presence=presence, pack=pk,
        )
    return fn, (blocks_sds, upd_sds, starts_sds)


def _fat_geometry_compiles(
    nb: int, w: int, geom, *, presence: bool, counting: bool,
    query: bool = False, batch: int | None = None,
) -> bool:
    """True if the fat kernel at ``geom`` compiles on the current device.

    On v5e, insert geometries inside the caps always pass (no insert
    OOM was ever measured inside them), and presence/counting
    geometries pass if listed in ``_VALIDATED_GEOMS`` with a big-fetch
    footprint the signature pins (:func:`_validated_kbjp_cap`); anything
    else — and everything on other TPU generations — is lowered +
    compiled AOT against ShapeDtypeStructs (no operand allocation) in a
    try/except. With ``batch`` the probe's update buffer carries the
    REAL runtime row count (ADVICE r5 #1 — the compile is then
    shape-identical to the first real call, so a passing probe cannot
    hide an operand-extent-dependent failure); results are cached per
    process AND per device kind on disk (ok only — see the
    ``_GEOM_DISK_CACHE`` note). CPU/GPU backends return True unchanged:
    the sweep path is never auto-selected off-TPU, and tests drive the
    kernel in interpret mode where Mosaic limits don't apply."""
    kind = _probe_env()
    if kind is None:
        return True
    J, R8, S, KJ, KBJ = geom
    pk = fat_pack(w, presence or query)  # as fat_kernel_shapes packs
    kbjp = _packed_rows(KBJ, pk)
    if any(v in kind for v in _VALIDATED_DEVICE_KINDS):
        if not (presence or counting or query):
            return True
        if not query:
            kname = "presence" if presence else "counting"
            sig = (J, R8, S, _packed_rows(KJ, pk))
            if sig in _VALIDATED_GEOMS[kname] and kbjp <= _validated_kbjp_cap(
                kname, sig
            ):
                return True
        # query geometries have NO hardware-validated signature set yet
        # (ISSUE 12 ships the kernel; the first TPU round will grow one)
        # — every query shape probe-compiles, on v5e too, and the result
        # persists in the on-disk cache like any other probe.
    fn, (blocks_sds, upd_sds, starts_sds) = fat_kernel_shapes(
        nb, w, geom, presence=presence, counting=counting, query=query,
        batch=batch,
    )
    key = (
        kind, nb, w, J, R8, S, KJ, KBJ, presence, counting, query,
        upd_sds.shape[0],
    )
    hit = _GEOM_PROBE_CACHE.get(key)
    if hit is not None:
        return hit
    key_str = "/".join(map(str, key[1:]))  # kind is the file, not the key
    if _geom_disk_get(kind, key_str):
        _GEOM_PROBE_CACHE[key] = True
        return True
    ok, last_exc = _probe_compile(fn, blocks_sds, upd_sds, starts_sds)
    if not ok:
        import warnings

        from tpubloom.obs import counters as obs_counters

        # visible in /metrics as tpubloom_geometry_probe_demotions_total
        # — a nonzero value on a TPU host says the process is running
        # demoted and a restart/investigation is warranted
        obs_counters.incr("geometry_probe_demotions")
        warnings.warn(
            f"tpubloom: fat-sweep geometry {geom} failed its probe "
            f"compile on device kind {kind!r}; this geometry is "
            f"disabled for the process (falling back to the next "
            f"shape / scatter path). Cause: {str(last_exc)[:300]}",
            RuntimeWarning,
            stacklevel=2,
        )
    _GEOM_PROBE_CACHE[key] = ok
    if ok:
        _geom_disk_put(kind, key_str)
    return ok


def choose_fat_params(
    nb: int, batch: int, words_per_block: int = 16, *, presence: bool = False,
    counting: bool = False,
):
    """(J, R8, S, KJ, KBJ) for the fat sweep, or None if the shape does
    not qualify (callers fall back to the legacy kernel / scatter).

    J = blocks per 128-lane fat row; R8 = fat rows per placement
    sub-tile; S = sub-tiles per grid step (DMA granularity); KJ = update
    slots per (substream, sub-tile) window (lambda + slack, multiple of
    8 — 6 sigma for presence, 8 sigma otherwise; see the loop comment);
    KBJ = rows per substream big-window fetch. Tiles cap at
    S*R8 = 1024 fat rows; within that, the measured per-kind body/volume
    caps below (r5: presence_geom_r5.json) separate compiling shapes
    from Mosaic scoped-VMEM OOMs."""
    import math

    w = words_per_block
    if 1 + w + (1 if presence else 0) > 128:
        # the update-stream row holds block id + W mask words (+ key idx
        # for presence) in 128 lanes; w=128 (block_bits=4096) can't fit —
        # mirror the legacy kernel's w+2>128 guard so a forced
        # insert_path="sweep" gets the clean ValueError, not a negative-
        # pad trace error from _fat_stream
        return None
    J = 128 // w
    if J < 1 or w * J != 128 or nb % J:
        return None
    NBJ = nb // J
    cap = 1024
    # lambda preference: the kernel is per-window-overhead-bound, not
    # MAC-bound, so PRESENCE takes the LARGEST feasible lambda — every
    # doubling halves the per-batch window count, and the measured
    # curve is monotone across the whole feasible range: lambda 128
    # (102.1 ms) -> 256 (66.2) at B=4M (presence_geom_r5.json), 256
    # (41.6M keys/s) -> 512 (44.0M) at B=8M (geom8m_r5.json). The
    # volume/KJ caps bound lambda from above (R8=1024 at B=4M and
    # lambda=1024 at B=16M are both cap-excluded), so "largest
    # feasible" stays inside the hardware-validated envelope.
    # Insert-only/counting keep lambda ~ 128: their lambda-optimum is
    # SHAPE-DEPENDENT and 128 is the only universally-safe point
    # measured. geom_ins_r5.json (B=8M, m=2^32): lambda=256 via R8=256
    # is +3.6% insert / +2.7% counting and flat at 512 — but the same
    # lambda=256 target at m=2^34 forces R8=1024 (4x placement MACs/
    # key) and measured -12% (45.5M vs 52.0M — both rows in
    # streaming_r5.json), so a
    # global target of 256 regresses the config-3 spec point. A
    # per-(nb, B) tuned table is possible future work; presence is
    # different (largest-feasible, measured monotone at every shape
    # tried) because halved window count dominates its MAC growth.
    lam_target = 7
    candidates = []
    for r8 in (32, 64, 128, 256, 512, 1024):
        if r8 > NBJ or NBJ % r8:
            continue
        lam = batch * r8 // nb
        if lam < 8:
            continue
        score = -lam if presence else abs(math.log2(max(lam, 1)) - lam_target)
        candidates.append((score, r8, lam))
    # feasibility (grid depth, lane columns, VMEM) is checked per
    # candidate, best score first — a smaller R8 may qualify where the
    # score-best one cannot (e.g. tiny filters where P8 // S < 2)
    for _, R8, lam in sorted(candidates):
        # window slack: presence windows run 6 sigma (measured r5,
        # benchmarks/out/kj_slack_r5.json: 41.9M vs 39.8M keys/s at 8
        # sigma — every slack slot is paid in kernel slot work AND in
        # the unsort; 4 sigma overflows ~per batch and collapses to the
        # scatter fallback, 26.1M). Insert keeps 8 sigma: 6 sigma was
        # re-measured a wash (67.2M vs 67.8M, same artifact — no unsort
        # side, slimmer windows). Counting keeps 8 sigma untested.
        # Overflow is correctness-safe at any slack —
        # _fat_window_overflow routes the batch to the scatter path.
        slack = 6 if presence else 8
        kj_raw = max(
            16, (lam + max(16, int(slack * math.sqrt(lam))) + 7) // 8 * 8
        )
        if kj_raw > 1024:
            # a KJ cap at/below mean occupancy would overflow every
            # window and pay the whole sort+stream build only to fall
            # back to scatter — mirror the legacy batch//P < kmax guard
            continue
        KJ = kj_raw
        P8 = NBJ // R8
        for s in (8, 4, 2, 1):
            if P8 % s or s * R8 > cap or P8 // s < 2:
                continue
            # Mosaic's scoped-VMEM stack grows with the fully-unrolled
            # S*J*PACK inner-body count AND each body's [KJP, R8]
            # matmul operands. Bounds are measured per KERNEL KIND,
            # each just above the largest hardware-validated shape of
            # that kind and below its smallest measured OOM:
            # * presence (r5 extraction kernel,
            #   benchmarks/out/presence_geom_r5.json + the B-sweep OOM
            #   point): compiles at 128 bodies / 2.10M volume, 64
            #   bodies / 3.41M, and 128 bodies / 1.70M; OOMs at 128
            #   bodies / 3.41M (B=8M chooser corner — caught by the
            #   clean r5 B-sweep, benchmarks/out/b_sweep_r5.json), 256
            #   bodies / 4.19M, and 32 bodies / 6.03M. The bound is
            #   JOINT: volume <= 3.5M overall AND volume <= 2.2M once
            #   bodies exceed 64 (the scoped stack grows with both).
            #   (The r4 G-matmul kernel OOMed at 128 bodies outright;
            #   the extraction kernel's scoped stack is much smaller.)
            #   The bodies bound also keeps slot columns t*J+j within
            #   the 128-lane presence tile (s * J <= 128 always holds
            #   at pack=4 since s*J*pk <= 128 => s*J <= 32; at pack=1,
            #   w >= 32 so s*J <= bodies/1 <= 128 with J <= 4).
            # * counting: plane expansions OOM at 4.2M units
            #   (J=16/R8=512 requested 17.5M scoped), 2.1M validated.
            # * plain insert: bit-exact at 4.2M (probed r4); its bound
            #   only fences untested corners.
            pk = fat_pack(w, presence)
            bodies = s * J * pk
            # bodies bound per kernel kind: insert validated at 256
            # bodies (B=8M, (128, 8) — ran at 67.8M keys/s r5);
            # counting OOMs at 256 bodies even at 2.10M volume (B=8M
            # probe, r5 — its nibble plane expansions out-stack the
            # insert kernel at equal geometry) and is validated at 128;
            # presence validated at 128.
            if bodies > (256 if not (presence or counting) else 128):
                continue
            volume = bodies * _packed_rows(KJ, pk) * R8
            cap_v = (
                3_500_000 if presence
                else 2_200_000 if counting
                else 4_300_000
            )
            if presence and bodies > 64:
                cap_v = 2_200_000  # joint bound — see matrix above
            if volume > cap_v:
                continue
            kbj = ((lam * s + KJ + 64 + 7) // 8) * 8
            # scoped-VMEM estimate: double-buffered windows + block tiles
            # (the window buffers hold PACKED rows — 4 updates per
            # 128-lane row when the fields fit a 32-lane stride)
            sup_rows = _packed_rows(kbj, fat_pack(w, presence))
            if (
                2 * J * sup_rows * 128 * 4 + 4 * (s * R8 * 128 * 4)
                <= 9 * 1024 * 1024
            ):
                geom = (J, R8, s, KJ, kbj)
                if not _fat_geometry_compiles(
                    nb, w, geom, presence=presence, counting=counting,
                    batch=batch,
                ):
                    continue  # unvalidated device generation: next shape
                return geom
    return None


def _expand_bits(m: jnp.ndarray, rows: int, w: int) -> jnp.ndarray:
    """[rows, w] packed u32 words -> [rows, w*32] 0/1 planes, b-major
    (column c = b*w + word holds bit b of that word)."""
    colC = lax.broadcasted_iota(jnp.int32, (rows, w * 32), 1)
    rep = jnp.concatenate([m] * 32, axis=1)
    return (rep >> (colC // w).astype(jnp.uint32)) & _u32(1)


def _pack_planes(present_bf16: jnp.ndarray, w: int) -> jnp.ndarray:
    """[rows, w*32] 0/1 bf16 planes -> [rows, w] u32 words via exact
    matmuls (8-bit quarters then 16-bit halves; every operand/result is
    integer-exact in the matmul dtype)."""
    ccol = lax.broadcasted_iota(jnp.int32, (w * 32, 4 * w), 0)
    hcol = lax.broadcasted_iota(jnp.int32, (w * 32, 4 * w), 1)
    b_of_c = ccol // w
    w_of_c = lax.rem(ccol, w)
    pack_w = jnp.where(
        (w_of_c + (b_of_c // 8) * w) == hcol,
        (1 << lax.rem(b_of_c, 8)).astype(jnp.float32),
        jnp.float32(0),
    ).astype(jnp.bfloat16)
    quarters = lax.dot_general(
        present_bf16, pack_w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(jnp.bfloat16)
    qcol = lax.broadcasted_iota(jnp.int32, (4 * w, w), 0)
    wcol = lax.broadcasted_iota(jnp.int32, (4 * w, w), 1)
    q_of = qcol // w
    w_of = lax.rem(qcol, w)
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


def _fat_kernel(
    starts_ref,  # SMEM [J * P8 + 1] i32 (scalar prefetch)
    upd_ref,  # ANY [BtotP, 128]: PACK updates/row at 128/PACK-lane stride
    blocks_ref,  # VMEM [S * R8, 128] fat rows (auto-streamed)
    *rest,  # out_ref [, pres_ref], sup_ref, sems
    R8: int,
    S: int,
    KJ: int,
    KBJ: int,
    P8: int,
    W: int,
    J: int,
    NBJ: int,
    PRES: bool,
    PACK: int = 1,
):
    if PRES:
        out_ref, pres_ref, sup_ref, sems = rest
    else:
        out_ref, sup_ref, sems = rest
        pres_ref = None
    p = pl.program_id(0)
    num_p = pl.num_programs(0)
    STRIDE = 128 // PACK
    KJP = _packed_rows(KJ, PACK)  # window fetch rows (packed units)
    KBJP = _packed_rows(KBJ, PACK)  # big fetch rows (packed units)

    def a_big(j, pp):
        return ((starts_ref[j * P8 + pp * S] // PACK) // _ALIGN) * _ALIGN

    def fetch(slot, pp):
        for j in range(J):
            pltpu.make_async_copy(
                upd_ref.at[pl.ds(a_big(j, pp), KBJP), :],
                sup_ref.at[slot, j],
                sems.at[slot, j],
            ).start()

    def wait(slot):
        for j in range(J):
            pltpu.make_async_copy(
                upd_ref.at[pl.ds(0, KBJP), :],
                sup_ref.at[slot, j],
                sems.at[slot, j],
            ).wait()

    slot = lax.rem(p, 2)

    @pl.when(p == 0)
    def _():
        fetch(0, 0)

    @pl.when(p + 1 < num_p)
    def _():
        fetch(1 - slot, p + 1)

    wait(slot)
    KJC = PACK * KJP  # unpacked update slots per window
    # presence slots live in a [KJC, 128] tile per grid step: slot
    # (u, packed row r) of window (j, q=p*S+t) at row u*KJP + r,
    # column t*J + j (requires S*J <= 128 — chooser-enforced). ONE
    # [KJC, 128] accumulator: per-slot values are computed at [KJP, 1]
    # (idxp1 stays a raw lane slice — those cannot sublane-concat, but
    # their COMPUTED where() outputs can), concatenated u-major to match
    # the tile row order, and merged with a single [KJC, 128] select/OR
    # per window (4 separate [KJP, 128] chains measurably pay 4x the
    # instruction issue on this overhead-bound kernel).
    pres_acc = jnp.zeros((PACK * KJP, 128), jnp.uint32) if PRES else None
    colsR = lax.broadcasted_iota(jnp.int32, (KJP, R8), 1)
    colpu = (
        lax.broadcasted_iota(jnp.int32, (KJP, 128), 1) if PRES else None
    )
    iota_r = lax.broadcasted_iota(jnp.int32, (KJP, 1), 0)
    for t in range(S):
        sl = pl.ds(t * R8, R8)
        tile = blocks_ref[sl, :]  # [R8, 128] pre-update fat rows
        base_rf = (p * S + t) * R8
        deltas = []
        for j in range(J):
            qi = j * P8 + p * S + t
            skey0 = _u32(j * NBJ) + _u32(base_rf)
            rel = ((starts_ref[qi] // PACK) // _ALIGN) * _ALIGN - a_big(j, p)
            rel = jnp.clip(rel, 0, KBJP - KJP)
            sub0 = sup_ref[slot, j, pl.ds(rel, KJP), :]  # [KJP, 128]
            a0 = a_big(j, p) + rel  # packed-row units
            end = starts_ref[qi + 1]
            # PACK update slots per fetched row, slot u at lanes
            # [u*STRIDE, u*STRIDE + 1 + W (+1)). Mosaic cannot
            # sublane-concat lane-SLICED vectors ("offset mismatch on
            # non-concat dimension"), but COMPUTED one-hots and
            # bit-planes concat fine — so each slot builds its own
            # [KJP, *] oh/bits and the window still runs ONE
            # KJC-contraction placement matmul (per-slot matmuls at
            # M=KJP measured 15% SLOWER end-to-end: the DMA they were
            # meant to amortize was already overlapped).
            # PACK=1 reduces to the original single-window pass.
            ohs, bitss = [], []
            for u in range(PACK):
                base = u * STRIDE
                rl = (sub0[:, base : base + 1] - skey0).astype(jnp.int32)
                ohs.append(
                    jnp.where(rl == colsR, jnp.float32(1), jnp.float32(0))
                )
                bitss.append(
                    _expand_bits(sub0[:, base + 1 : base + 1 + W], KJP, W)
                )
            oh_f32 = (
                jnp.concatenate(ohs, axis=0) if PACK > 1 else ohs[0]
            )  # [KJC, R8]
            bits = (
                jnp.concatenate(bitss, axis=0) if PACK > 1 else bitss[0]
            )  # [KJC, W*32]
            cnt = lax.dot_general(
                oh_f32.astype(jnp.int8), bits.astype(jnp.int8),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # [R8, W*32]
            # NO in-kernel overflow chunks: a dynamic DMA loop in the body
            # defeats Mosaic's pipelining (measured +86% kernel time even
            # with zero iterations). Windows that overflow KJ (adversarial
            # duplicate skew only) are detected host-side from `starts`
            # and the WHOLE batch falls back to the sorted-scatter path
            # under lax.cond — see apply_fat_updates.
            present_pl = jnp.where(
                cnt > 0, jnp.float32(1), jnp.float32(0)
            ).astype(jnp.bfloat16)
            deltas.append(_pack_planes(present_pl, W))

            if PRES:
                # Pre-batch membership by OLD-ROW EXTRACTION, not a
                # G matmul: slot s's old block row is recovered nibble-
                # exact with the placement one-hot ([KJC, R8] @ [R8, 8W]
                # int8 — nibble values <= 15 times a 0/1 one-hot, i32
                # accumulation), then the membership test is
                # (old & mask) == mask on the nibble planes. This
                # replaced r4's G = mask_bits @ tilebits^T (a W*32-deep
                # contraction, 4x the MACs of this one) plus the
                # [R8, W*32] tile bit expansion and [KJC, W*32] npos
                # reduction that fed it — the two largest VPU surfaces
                # of the r4 presence budget (benchmarks/RESULTS_r5.md).
                # Slots whose row is outside this window extract row 0
                # garbage; `real` masks them below, as before.
                tj = tile[:, j * W : (j + 1) * W]  # [R8, W] u32
                tn = jnp.concatenate(
                    [
                        ((tj >> _u32(4 * n)) & _u32(15)).astype(jnp.int8)
                        for n in range(8)
                    ],
                    axis=1,
                )  # [R8, 8W] old-row nibbles
                rn = lax.dot_general(
                    oh_f32.astype(jnp.int8), tn, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32,
                )  # [KJC, 8W] per-slot old-row nibbles (one-hot-exact)
                rn_u = rn.astype(jnp.uint32)
                mns = []
                for u in range(PACK):
                    mu = sub0[:, u * STRIDE + 1 : u * STRIDE + 1 + W]
                    # computed shift/and outputs of the raw lane slice:
                    # lane-concat then sublane-concat both lower (the
                    # same pattern as the bits/one-hot builds above)
                    mns.append(
                        jnp.concatenate(
                            [(mu >> _u32(4 * n)) & _u32(15) for n in range(8)],
                            axis=1,
                        )
                    )
                mn = jnp.concatenate(mns, axis=0) if PACK > 1 else mns[0]
                okf = jnp.where(
                    (mn & rn_u) == mn, jnp.float32(1), jnp.float32(0)
                )
                hit = jnp.min(okf, axis=1, keepdims=True)  # [KJC, 1] f32
                vus = []
                for u in range(PACK):
                    # 8-aligned sublane slices of the COMPUTED hit
                    # (KJP % 8 == 0) lower fine; the raw idxp1 lane
                    # slice is used elementwise only. Each slot's value
                    # is SELECTED into its tile column BEFORE the
                    # sublane concat: a [KJP, 1] where() output keeps
                    # its source slice's lane-offset layout and Mosaic
                    # refuses to concat mismatched offsets ("offset
                    # mismatch on non-concat dimension"), while the
                    # [KJP, 128] where-broadcast is standard-layout.
                    hit_u = lax.slice_in_dim(hit, u * KJP, (u + 1) * KJP, axis=0)
                    idxp1 = sub0[
                        :, u * STRIDE + W + 1 : u * STRIDE + W + 2
                    ]  # [KJP, 1]
                    ipos = (a0 + iota_r) * PACK + u
                    real = (
                        (ipos >= starts_ref[qi]) & (ipos < end) & (idxp1 > 0)
                    )
                    hbit = jnp.where(
                        hit_u > 0.5, _u32(0x80000000), _u32(0)
                    )
                    v = jnp.where(real, idxp1 | hbit, _u32(0))
                    vus.append(jnp.where(colpu == t * J + j, v, _u32(0)))
                v128 = (
                    jnp.concatenate(vus, axis=0) if PACK > 1 else vus[0]
                )  # [KJC, 128], u-major — the tile's row order
                pres_acc = pres_acc | v128
        delta_fat = jnp.concatenate(deltas, axis=1)  # [R8, J*W = 128]
        out_ref[sl, :] = tile | delta_fat
    if PRES:
        pres_ref[:] = pres_acc


def fat_sweep_insert(
    blocks_fat: jnp.ndarray,
    upd: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    J: int,
    R8: int,
    S: int,
    KJ: int,
    KBJ: int,
    W: int,
    interpret: bool = False,
    with_presence: bool = False,
    pack: int = 1,
):
    """Apply a substream-sorted update stream to the fat-row block view.

    ``blocks_fat``: ``uint32[NB/J, 128]`` (reshape of the [NB, W] array);
    ``upd``: ``uint32[Btot, 128]`` sorted by skey (col 0), masks in cols
    1..W, original index + 1 in col W+1 (presence), ``>= KBJ + 8`` rows
    of sentinel tail padding; ``starts``: ``int32[J*P8 + 1]`` window
    boundaries, j-major. Returns the updated fat view, plus — with
    presence — ``uint32[P*KJC, 128]`` slot-value tiles, where
    ``KJC = pack * _packed_rows(KJ, pack)``: slot (u, packed row r) of
    window (j, q) at row ``(q // S)*KJC + u*KJP + r``, column
    ``(q % S)*J + j``, value ``idx+1 | was_present << 31``; 0 = empty
    slot. ``_fat_unsort_presence`` is the one consumer of this layout."""
    NB8, L = blocks_fat.shape
    assert L == 128
    P8 = NB8 // R8
    P = P8 // S
    kjc = pack * _packed_rows(KJ, pack)  # presence rows per grid step
    kbjp = _packed_rows(KBJ, pack)  # big-fetch rows (packed units)
    out_shape = jax.ShapeDtypeStruct((NB8, 128), jnp.uint32)
    out_spec = pl.BlockSpec((S * R8, 128), lambda p, *_: (p, 0))
    if with_presence:
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct((P * kjc, 128), jnp.uint32),
        )
        out_spec = (out_spec, pl.BlockSpec((kjc, 128), lambda p, *_: (p, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((S * R8, 128), lambda p, *_: (p, 0)),
        ],
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((2, J, kbjp, 128), jnp.uint32),
            pltpu.SemaphoreType.DMA((2, J)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(
            _fat_kernel,
            R8=R8, S=S, KJ=KJ, KBJ=KBJ, P8=P8, W=W, J=J, NBJ=NB8,
            PRES=with_presence, PACK=pack,
        ),
        out_shape=out_shape,
        grid_spec=grid_spec,
        input_output_aliases={2: 0},
        interpret=interpret,
    )
    return fn(starts, upd, blocks_fat)


def fat_pack(w: int, presence: bool) -> int:
    """Updates per 128-lane stream row. An update needs 1 (skey) + W
    (masks/counts) + 1 (idx, presence only) lanes; when that fits a
    32-lane stride, FOUR updates share each row — 4x fewer stream bytes
    for both the host-side build write and the kernel's window fetches.
    (Sub-128-lane arrays cannot shrink the stream instead: Mosaic pads
    their HBM layout to 128 lanes and then rejects manual-DMA slices —
    measured, benchmarks/lane_probe.py.)"""
    return 4 if 1 + w + (1 if presence else 0) <= 32 else 1


def _packed_rows(n_upd: int, pack: int) -> int:
    """Fetch/window length in PACKED rows covering ``n_upd`` updates plus
    the 8-aligned fetch floor (<= 7 rows) and the end-row straddle
    (1 row), rounded to a multiple of 8. pack=1 keeps the legacy
    unpacked geometry bit-for-bit."""
    if pack == 1:
        return n_upd
    return ((n_upd // pack + _ALIGN) + 7) // 8 * 8


def _fat_stream(
    skey_sorted, masks, idx_sorted, *, J, NBJ, P8, R8, KBJ, W, pack=1
):
    """Single-pass update-stream assembly for the fat sweep: one
    concatenate builds the [Btot, 128] buffer (multiple .at[].set()
    passes measurably cost ~2 GB of extra HBM writes each at B=4M).

    With ``pack`` > 1, consecutive sorted updates share each 128-lane
    row at a ``128 // pack``-lane stride (update u of packed row r is
    update ``r * pack + u`` of the sorted stream — a plain row-major
    fold, so one XLA reshape builds it). ``starts`` stays in UPDATE
    units; the kernel converts to packed rows."""
    B = masks.shape[0]
    cols = [skey_sorted.astype(jnp.uint32)[:, None], masks]
    ncols = 1 + W
    if idx_sorted is not None:
        cols.append(idx_sorted.astype(jnp.uint32)[:, None])
        ncols += 1
    core = jnp.concatenate(cols, axis=1)
    jq = jnp.arange(J * P8 + 1, dtype=jnp.int32)
    tgt = jnp.where(
        jq == J * P8, J * NBJ, (jq // P8) * NBJ + (jq % P8) * R8
    ).astype(jnp.int32)
    starts = jnp.searchsorted(skey_sorted.astype(jnp.int32), tgt).astype(
        jnp.int32
    )
    if pack == 1:
        pad = KBJ + _ALIGN
        # jnp.pad lowers to one fused write here; concatenating explicit
        # zero blocks measurably costs ~2x (2 GB array at B=4M)
        upd = jnp.pad(core, ((0, pad), (0, 128 - ncols)))
        upd = upd.at[B:, 0].set(jnp.uint32(J * NBJ))
        return upd, starts
    stride = 128 // pack
    kbjp = _packed_rows(KBJ, pack)
    btot_p = -(-B // pack) + kbjp + _ALIGN
    padrows = btot_p * pack - B
    wide = jnp.pad(core, ((0, padrows), (0, stride - ncols)))
    wide = wide.at[B:, 0].set(jnp.uint32(J * NBJ))
    return wide.reshape(btot_p, 128), starts


def _fat_window_overflow(starts, *, J, P8, S, KJ, KBJ, pack=1):
    """True if any (j, q) window cannot cover its slice from the clamped
    KJ-row fetch. The fat kernel has NO chunk loop (rows beyond the KJ
    window are silently never applied), so on overflow apply_fat_updates
    routes the WHOLE batch — insert AND presence — to the sorted-scatter
    branch under lax.cond; that branch is the only thing keeping
    overflowing batches correct. The packed arithmetic mirrors the
    kernel's exactly (same floor/clip in packed-row units)."""
    s = starts
    jq = jnp.arange(J * P8, dtype=jnp.int32)
    big_idx = (jq // P8) * P8 + ((jq % P8) // S) * S
    if pack == 1:
        a_big = (s[big_idx] // _ALIGN) * _ALIGN
        a = a_big + jnp.clip((s[jq] // _ALIGN) * _ALIGN - a_big, 0, KBJ - KJ)
        return jnp.max(s[jq + 1] - a) > KJ
    kjp = _packed_rows(KJ, pack)
    kbjp = _packed_rows(KBJ, pack)
    a_big = ((s[big_idx] // pack) // _ALIGN) * _ALIGN
    r4 = ((s[jq] // pack) // _ALIGN) * _ALIGN
    a = a_big + jnp.clip(r4 - a_big, 0, kbjp - kjp)
    need_end = -(-(s[jq + 1]) // pack)  # ceil in packed rows
    return jnp.max(need_end - a) > kjp


def _fat_unsort_presence(presb, starts, B, *, J, NBJ, P8, R8, S, KJ, KBJ):
    """Presence tiles -> bool[B] in original key order via the vkey
    single-column unsort (idx+1 rides bits 1.., verdict the LSB; empty
    slots sink to the tail). ``KJ`` here is the slots per window (KJC =
    pack * KJP when the stream is packed); window (j, q) rides column
    t*J + j of its grid step's tile."""
    P = P8 // S
    jq = jnp.arange(J * P8, dtype=jnp.int32)
    j = jq // P8
    q = jq % P8
    p0 = q // S
    t = q % S
    presT = presb.reshape(P, KJ, 128).transpose(0, 2, 1).reshape(P * 128, KJ)
    v = presT[p0 * 128 + t * J + j]  # [J*P8, KJ]
    vkey = jnp.where(
        v == 0,
        _u32(0xFFFFFFFE),  # even: empty slots must read as hit=0
        ((v & _u32(0x7FFFFFFF)) << _u32(1)) | (v >> _u32(31)),
    ).reshape(-1)
    (skey,) = lax.sort((vkey,), num_keys=1)
    return (skey[:B] & _u32(1)) == 1


def apply_fat_updates(
    blocks: jnp.ndarray,
    blk: jnp.ndarray,
    bit: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    block_bits: int,
    params,
    interpret: bool | None = None,
    idx: jnp.ndarray | None = None,
    storage_fat: bool = False,
):
    """Fat-sweep counterpart of :func:`apply_blocked_updates`; ``params``
    from :func:`choose_fat_params`.

    Windows that overflow their KJ fetch (adversarial duplicate skew —
    uniform keys sit 8 sigma below) route the WHOLE batch to the
    sorted-scatter path under ``lax.cond``: the kernel itself carries no
    chunk loop (a dynamic DMA loop in the body measurably defeats
    Mosaic's pipelining even at zero iterations).

    Returns the new blocks ([NB, W]); with ``idx`` (original key
    indices, 1-based — presence mode) returns ``(new_blocks,
    present[B])`` where ``present`` is each key's PRE-batch membership.

    Presence CONTRACT (same as the legacy kernel): invalid entries
    (``valid`` False) must form a TAIL SUFFIX of the batch
    (tpubloom.filter._pack_padded guarantees this). Invalid keys emit no
    presence slot, so a mid-batch invalid entry would shift every later
    key's verdict by one in the index-sorted unsort; tail padding keeps
    valid indices contiguous (1..V) and padded entries correctly read
    False from the empty-slot fillers.

    ``storage_fat``: ``blocks`` is already the fat [NB/J, 128] view and
    the fat view is returned — no reshape at the kernel boundary (XLA's
    tiled HBM layouts make [NB, W] <-> fat reshapes REAL copies, ~26 ms
    per pass at m=2^32; persistent filters keep their storage fat).
    """
    w = block_bits // 32
    J0, R8, S, KJ, KBJ = params
    nb = blocks.size // w
    B = blk.shape[0]
    J = J0
    NBJ = nb // J
    P8 = NBJ // R8
    interp = jax.default_backend() == "cpu" if interpret is None else interpret
    pack = fat_pack(w, idx is not None)
    with jax.named_scope(SORT_SCOPE):
        blkv = jnp.where(valid, blk, nb)
        j_of = (blkv % J).astype(jnp.uint32)
        rf_of = (blkv // J).astype(jnp.uint32)
        skey = jnp.where(valid, j_of * NBJ + rf_of, _u32(J * NBJ))
        cols, nbits, packed = _pack_positions(bit, block_bits, bit.shape[-1])
        extra = (idx,) if idx is not None else ()
        sorted_cols = lax.sort((skey,) + cols + extra, num_keys=1)
        ss = sorted_cols[0]
        pcols = sorted_cols[1:-1] if idx is not None else sorted_cols[1:]
        bit_sorted = _unpack_positions(
            pcols, block_bits, bit.shape[-1], nbits, packed
        )
        masks = blocked.build_masks(bit_sorted, w)
        idx_sorted = sorted_cols[-1] if idx is not None else None
        upd, starts = _fat_stream(
            ss, masks, idx_sorted, J=J, NBJ=NBJ, P8=P8, R8=R8, KBJ=KBJ, W=w,
            pack=pack,
        )
        overflow = _fat_window_overflow(
            starts, J=J, P8=P8, S=S, KJ=KJ, KBJ=KBJ, pack=pack
        )

    def to_fat(bl):
        return bl if storage_fat else bl.reshape(NBJ, 128)

    def from_fat(bl_fat):
        return bl_fat if storage_fat else bl_fat.reshape(nb, w)

    def _scatter_coords():
        """(row, masks) for the fallback in whichever view ``blocks``
        is stored — the fat fold keeps the fallback reshape-free
        (a fat <-> [NB, W] reshape is a real copy on TPU)."""
        masks_orig = blocked.build_masks(bit, w)
        if storage_fat:
            return blocked.fat_fold_masks(blk, masks_orig, J)
        return blk, masks_orig

    if idx is None:

        @jax.named_scope(SWEEP_SCOPE)
        def fat_branch(ops):
            bl, u, st = ops
            return from_fat(
                fat_sweep_insert(
                    to_fat(bl), u, st,
                    J=J, R8=R8, S=S, KJ=KJ, KBJ=KBJ, W=w, interpret=interp,
                    pack=pack,
                )
            )

        @jax.named_scope(SWEEP_SCOPE)
        def scatter_branch(ops):
            bl, u, st = ops
            row, masks_orig = _scatter_coords()
            return blocked.blocked_insert(bl, row, masks_orig, valid)

        return lax.cond(overflow, scatter_branch, fat_branch, (blocks, upd, starts))

    def fat_branch(ops):
        bl, u, st = ops
        with jax.named_scope(SWEEP_SCOPE):
            new_fat, presb = fat_sweep_insert(
                to_fat(bl), u, st,
                J=J, R8=R8, S=S, KJ=KJ, KBJ=KBJ, W=w,
                interpret=interp, with_presence=True, pack=pack,
            )
        with jax.named_scope(UNSORT_SCOPE):
            present = _fat_unsort_presence(
                presb, st, B, J=J, NBJ=NBJ, P8=P8, R8=R8, S=S,
                KJ=pack * _packed_rows(KJ, pack), KBJ=KBJ,
            )
        return from_fat(new_fat), present

    @jax.named_scope(SWEEP_SCOPE)
    def scatter_branch(ops):
        bl, u, st = ops
        row, masks_orig = _scatter_coords()
        nrows = bl.shape[0]
        rows = bl[jnp.minimum(jnp.where(valid, row, 0), nrows - 1)]
        hit = jnp.all((rows & masks_orig) == masks_orig, axis=-1)
        present = hit & valid
        out = blocked.blocked_insert(bl, row, masks_orig, valid)
        return out, present

    return lax.cond(overflow, scatter_branch, fat_branch, (blocks, upd, starts))


def _fat_count_kernel(
    starts_ref,  # SMEM [J * P8 + 1] i32 (scalar prefetch)
    upd_ref,  # ANY [Btot, 128]: col 0 skey, 1..W packed nibble counts
    blocks_ref,  # VMEM [S * R8, 128] fat counter rows (auto-streamed)
    out_ref,  # VMEM [S * R8, 128]
    sup_ref,  # VMEM scratch [2, J, KBJ, 128] u32
    sems,  # DMA sems [2, J]
    *,
    R8: int,
    S: int,
    KJ: int,
    KBJ: int,
    P8: int,
    W: int,
    J: int,
    NBJ: int,
    INCREMENT: bool,
    PACK: int = 1,
):
    """Fat-row blocked-counting sweep: saturating nibble add/subtract on
    the [NB/J, 128] counter view (same substream-sorted stream layout as
    :func:`_fat_kernel`, including the PACK-updates-per-row stream; same
    one-clamp-per-batch semantics as :func:`_count_kernel` — counts are
    additive so there is no merge or presence machinery, and like the fat
    bit kernel there is NO in-kernel chunk loop: window overflow routes
    the batch to the scatter fallback host-side)."""
    p = pl.program_id(0)
    num_p = pl.num_programs(0)
    STRIDE = 128 // PACK
    KJP = _packed_rows(KJ, PACK)
    KBJP = _packed_rows(KBJ, PACK)

    def a_big(j, pp):
        return ((starts_ref[j * P8 + pp * S] // PACK) // _ALIGN) * _ALIGN

    def fetch(slot, pp):
        for j in range(J):
            pltpu.make_async_copy(
                upd_ref.at[pl.ds(a_big(j, pp), KBJP), :],
                sup_ref.at[slot, j],
                sems.at[slot, j],
            ).start()

    def wait(slot):
        for j in range(J):
            pltpu.make_async_copy(
                upd_ref.at[pl.ds(0, KBJP), :],
                sup_ref.at[slot, j],
                sems.at[slot, j],
            ).wait()

    slot = lax.rem(p, 2)

    @pl.when(p == 0)
    def _():
        fetch(0, 0)

    @pl.when(p + 1 < num_p)
    def _():
        fetch(1 - slot, p + 1)

    wait(slot)
    CPB = W * 8  # nibble planes per block
    colC = lax.broadcasted_iota(jnp.int32, (KJP, CPB), 1)
    colsR = lax.broadcasted_iota(jnp.int32, (KJP, R8), 1)
    tcolC = lax.broadcasted_iota(jnp.int32, (R8, CPB), 1)
    # block-diagonal plane->word pack weights, one [J*CPB, 128] matrix
    # per byte q: plane (j, n*W + w) contributes 1 (n even) or 16 (n
    # odd) to lane j*W + w when n // 2 == q (same exact-byte matmul
    # trick as _count_kernel, widened to the full fat row so each
    # sub-tile packs with 4 matmuls instead of 4*J narrow ones)
    pcJ = lax.broadcasted_iota(jnp.int32, (J * CPB, 128), 0)
    lnJ = lax.broadcasted_iota(jnp.int32, (J * CPB, 128), 1)
    j_of = pcJ // CPB
    n_of = lax.rem(pcJ, CPB) // W
    w_of = lax.rem(pcJ, W)
    lane_match = lnJ == j_of * W + w_of
    pack_qs = []
    for q in range(4):
        pack_qs.append(
            jnp.where(
                lane_match & (n_of // 2 == q),
                jnp.where(lax.rem(n_of, 2) == 0, jnp.float32(1), jnp.float32(16)),
                jnp.float32(0),
            ).astype(jnp.bfloat16)
        )
    for t in range(S):
        sl = pl.ds(t * R8, R8)
        tile = blocks_ref[sl, :]  # [R8, 128] pre-update fat counter rows
        base_rf = (p * S + t) * R8
        news = []
        for j in range(J):
            qi = j * P8 + p * S + t
            skey0 = _u32(j * NBJ) + _u32(base_rf)
            rel = ((starts_ref[qi] // PACK) // _ALIGN) * _ALIGN - a_big(j, p)
            rel = jnp.clip(rel, 0, KBJP - KJP)
            sub = sup_ref[slot, j, pl.ds(rel, KJP), :]  # [KJP, 128]
            # per-slot COMPUTED one-hots/nibble-planes concat along the
            # contraction axis (raw lane slices cannot sublane-concat in
            # Mosaic, computed values can), so the window still runs ONE
            # KJC-contraction matmul. PACK=1 reduces to the original
            # single pass.
            ohs, nibfs = [], []
            for u in range(PACK):
                base = u * STRIDE
                rl = (sub[:, base : base + 1] - skey0).astype(jnp.int32)
                ohs.append(
                    jnp.where(
                        rl == colsR, jnp.float32(1), jnp.float32(0)
                    ).astype(jnp.bfloat16)
                )  # [KJP, R8]; sentinels match nothing
                m = sub[:, base + 1 : base + 1 + W]  # [KJP, W] nibbles
                rep = jnp.concatenate([m] * 8, axis=1)  # [KJP, CPB]
                nib = (
                    rep >> ((colC // W).astype(jnp.uint32) * _u32(4))
                ) & _u32(15)
                nibfs.append(
                    nib.astype(jnp.int32)
                    .astype(jnp.float32)
                    .astype(jnp.bfloat16)
                )
            oh = jnp.concatenate(ohs, axis=0) if PACK > 1 else ohs[0]
            nibf = jnp.concatenate(nibfs, axis=0) if PACK > 1 else nibfs[0]
            cnts = lax.dot_general(
                oh, nibf, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [R8, CPB], exact (<= 15 * KJP * PACK < 2^24)
            acc = jnp.minimum(cnts, jnp.float32(16))
            tj = tile[:, j * W : (j + 1) * W]
            trep = jnp.concatenate([tj] * 8, axis=1)  # [R8, CPB]
            old = (trep >> ((tcolC // W).astype(jnp.uint32) * _u32(4))) & _u32(15)
            oldf = old.astype(jnp.int32).astype(jnp.float32)
            if INCREMENT:
                new = jnp.minimum(oldf + acc, jnp.float32(15))
            else:
                new = jnp.maximum(oldf - acc, jnp.float32(0))
            news.append(new.astype(jnp.bfloat16))  # <= 15, bf16-exact
        new_all = jnp.concatenate(news, axis=1)  # [R8, J*CPB]
        packed = jnp.zeros((R8, 128), jnp.uint32)
        for q in range(4):
            byte = lax.dot_general(
                new_all, pack_qs[q], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [R8, 128] f32-exact bytes
            packed = packed | (
                byte.astype(jnp.int32).astype(jnp.uint32) << _u32(8 * q)
            )
        out_ref[sl, :] = packed


def fat_sweep_counter(
    blocks_fat: jnp.ndarray,
    upd: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    J: int,
    R8: int,
    S: int,
    KJ: int,
    KBJ: int,
    W: int,
    increment: bool,
    interpret: bool = False,
    pack: int = 1,
) -> jnp.ndarray:
    """Apply a substream-sorted nibble-count stream to the fat counter
    view. Same stream contract as :func:`fat_sweep_insert` with cols
    1..W carrying packed 4-bit per-counter multiplicities instead of OR
    masks."""
    NB8, L = blocks_fat.shape
    assert L == 128
    P8 = NB8 // R8
    P = P8 // S
    kbjp = _packed_rows(KBJ, pack)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((S * R8, 128), lambda p, *_: (p, 0)),
        ],
        out_specs=pl.BlockSpec((S * R8, 128), lambda p, *_: (p, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, J, kbjp, 128), jnp.uint32),
            pltpu.SemaphoreType.DMA((2, J)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(
            _fat_count_kernel,
            R8=R8, S=S, KJ=KJ, KBJ=KBJ, P8=P8, W=W, J=J, NBJ=NB8,
            INCREMENT=increment, PACK=pack,
        ),
        out_shape=jax.ShapeDtypeStruct((NB8, 128), jnp.uint32),
        grid_spec=grid_spec,
        input_output_aliases={2: 0},
        interpret=interpret,
    )
    return fn(starts, upd, blocks_fat)


def apply_fat_counter_updates(
    blocks: jnp.ndarray,
    blk: jnp.ndarray,
    cpos: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    counters_per_block: int,
    k: int,
    increment: bool,
    params,
    interpret: bool | None = None,
    storage_fat: bool = False,
) -> jnp.ndarray:
    """Fat-sweep counterpart of :func:`apply_counter_updates`; ``params``
    from :func:`choose_fat_params` (presence=False — counting has no
    fused-presence variant). Window overflow (adversarial duplicate
    skew) routes the WHOLE batch to the flat scatter fallback under
    ``lax.cond``, exactly like :func:`apply_fat_updates`.

    ``storage_fat``: ``blocks`` is already the fat [NB/J, 128] view and
    the fat view is returned."""
    from tpubloom.ops import counting

    J0, R8, S, KJ, KBJ = params
    cpb = counters_per_block
    w = cpb // 8
    nb = blocks.size // w
    B = blk.shape[0]
    J = J0
    NBJ = nb // J
    P8 = NBJ // R8
    interp = jax.default_backend() == "cpu" if interpret is None else interpret
    blkv = jnp.where(valid, blk, nb)
    j_of = (blkv % J).astype(jnp.uint32)
    rf_of = (blkv // J).astype(jnp.uint32)
    skey = jnp.where(valid, j_of * NBJ + rf_of, _u32(J * NBJ))
    cols, nbits, packed = _pack_positions(cpos, cpb, k)
    sorted_cols = lax.sort((skey,) + cols, num_keys=1)
    ss = sorted_cols[0]
    cpos_s = _unpack_positions(sorted_cols[1:], cpb, k, nbits, packed)
    # per-key multiplicity of each counter, 4-bit nibbles in the counter
    # storage (word, nibble) layout (multiplicity <= k <= 15)
    planes = jnp.zeros((B, cpb), jnp.uint32)
    iota_c = lax.broadcasted_iota(jnp.uint32, (B, cpb), 1)
    for i in range(k):
        planes = planes + (cpos_s[:, i : i + 1] == iota_c).astype(jnp.uint32)
    pw = planes.reshape(B, w, 8)
    shifts = (jnp.arange(8, dtype=jnp.uint32) * 4)[None, None, :]
    cnt_words = jnp.sum(pw << shifts, axis=2, dtype=jnp.uint32)  # [B, W]
    pack = fat_pack(w, False)
    upd, starts = _fat_stream(
        ss, cnt_words, None, J=J, NBJ=NBJ, P8=P8, R8=R8, KBJ=KBJ, W=w,
        pack=pack,
    )
    overflow = _fat_window_overflow(
        starts, J=J, P8=P8, S=S, KJ=KJ, KBJ=KBJ, pack=pack
    )

    def fat_branch(ops):
        bl, u, st = ops
        out = fat_sweep_counter(
            bl if storage_fat else bl.reshape(NBJ, 128), u, st,
            J=J, R8=R8, S=S, KJ=KJ, KBJ=KBJ, W=w,
            increment=increment, interpret=interp, pack=pack,
        )
        return out if storage_fat else out.reshape(nb, w)

    def scatter_branch(ops):
        bl, u, st = ops
        gpos = (blk[:, None] * cpb + cpos.astype(jnp.int32)).astype(jnp.int32)
        valid_k = jnp.broadcast_to(valid[:, None], gpos.shape)
        out = counting.counter_update(
            bl.reshape(-1), gpos.ravel(), valid_k.ravel(), increment=increment
        )
        return out.reshape(blocks.shape)

    return lax.cond(overflow, scatter_branch, fat_branch, (blocks, upd, starts))


def make_sweep_insert_fn(
    config, *, interpret: bool | None = None, with_presence: bool = False,
    storage_fat: bool = False,
):
    """Pure ``(blocks, keys_u8, lengths) -> blocks`` blocked insert via the
    partition sweep. Bit-identical to
    :func:`tpubloom.filter.make_blocked_insert_fn` (same blocked spec).

    With ``with_presence`` the function returns ``(blocks, present)``
    where ``present[i]`` says whether key i was in the filter BEFORE this
    batch (test-and-insert — the semantics of the reference's Lua add
    script, which returns prior membership). Within-batch duplicates all
    report the pre-batch state. Requires batch padding (lengths < 0) to
    sit at the TAIL of the batch (tpubloom.filter._pack_padded
    guarantees this); padded entries return False.

    ``storage_fat``: blocks are the fat [NB/J, 128] view in AND out (the
    persistent-filter layout; avoids reshape copies at the kernel
    boundary). Batches the fat kernel cannot take reshape to the
    logical view internally.
    """
    nb, bb, w = config.n_blocks, config.block_bits, config.words_per_block
    k, seed, bh = config.k, config.seed, config.block_hash

    def insert(blocks, keys_u8, lengths):
        B = keys_u8.shape[0]
        fat_shape = blocks.shape if storage_fat else None
        # legacy-kernel shape guards apply only when the fat sweep does
        # not take the batch (apply_blocked_updates / the presence branch
        # below prefer it)
        has_fat = choose_fat_params(nb, B, w, presence=with_presence) is not None
        R, KMAX = choose_params(nb, B)
        if not has_fat and (nb % R != 0 or w + 2 > 128 or R % 32 != 0):
            # partitions must tile the array exactly (or trailing blocks
            # would silently never receive updates), the 128-lane update
            # row must fit block id + W mask words + key idx, and R must
            # be a multiple of 32 for the Kronecker one-hot split
            raise ValueError(
                f"sweep insert does not support this shape (n_blocks={nb}, "
                f"R={R}, words_per_block={w}) — use insert_path='scatter'"
            )
        if with_presence and not has_fat and (nb // R) * KMAX < B:
            # the presence output has one slot per chunk-0 window entry;
            # batches larger than P*KMAX cannot all be answered (auto
            # never picks such shapes — only a forced 'sweep' gets here)
            raise ValueError(
                f"sweep test-and-insert needs P*KMAX >= batch "
                f"({(nb // R) * KMAX} < {B}) — use insert_path='scatter'"
            )
        P = nb // R
        interp = (
            jax.default_backend() == "cpu" if interpret is None else interpret
        )
        valid = lengths >= 0
        blk, bit = blocked.block_positions(
            keys_u8, jnp.maximum(lengths, 0),
            n_blocks=nb, block_bits=bb, k=k, seed=seed, block_hash=bh,
        )
        if not with_presence:
            fat = choose_fat_params(nb, B, w)
            if fat is not None:
                return apply_fat_updates(
                    blocks, blk, bit, valid,
                    block_bits=bb, params=fat, interpret=interpret,
                    storage_fat=storage_fat,
                )
            out = apply_blocked_updates(
                blocks.reshape(nb, w) if storage_fat else blocks,
                blk, bit, valid, block_bits=bb, interpret=interpret,
            )
            return out.reshape(fat_shape) if storage_fat else out
        fat = choose_fat_params(nb, B, w, presence=True)
        if fat is not None:
            idx0 = jnp.arange(1, B + 1, dtype=jnp.uint32)  # 0 = empty slot
            return apply_fat_updates(
                blocks, blk, bit, valid,
                block_bits=bb, params=fat, interpret=interpret, idx=idx0,
                storage_fat=storage_fat,
            )
        if storage_fat:
            blocks = blocks.reshape(nb, w)
        with jax.named_scope(SORT_SCOPE):
            blk = jnp.where(valid, blk, nb)
            cols, nbits, packed = _pack_positions(bit, bb, k)
            idx0 = jnp.arange(1, B + 1, dtype=jnp.uint32)  # 0 = filler
            cols = cols + (idx0,)
            sorted_cols = lax.sort((blk,) + cols, num_keys=1)
            bs = sorted_cols[0]
            bit_sorted = _unpack_positions(
                sorted_cols[1:-1], bb, k, nbits, packed
            )
            masks = blocked.build_masks(bit_sorted, w)
            # sentinel rows carry zero masks (their positions are real
            # hash bits of padding keys; they never reach a partition,
            # but keep the invariant obvious)
            starts, upd = _stream_scaffold(bs, nb, P, R, KMAX)
            upd = upd.at[:B, 1 : w + 1].set(masks)

            upd = upd.at[:B, w + 1].set(sorted_cols[-1])
        # chunk-0 windows cover [align8(starts[p]), +KMAX); a partition
        # whose slice exceeds that emits no presence for the overflow —
        # rare (KMAX covers lambda+8sigma; needs adversarial duplicate
        # skew), handled by a gather-query fallback on the PRE-batch
        # array, computed under lax.cond so the common path never pays.
        span = starts[1:] - (starts[:-1] // _ALIGN) * _ALIGN
        overflow = jnp.max(span) > KMAX

        def gather_presence():
            rows = blocks[jnp.minimum(blk, nb - 1)]
            masks_orig = blocked.build_masks(bit, w)
            hit = jnp.all((rows & masks_orig) == masks_orig, axis=-1)
            return hit & valid & (blk < nb)

        with jax.named_scope(SWEEP_SCOPE):
            presence_fb = lax.cond(
                overflow,
                gather_presence,
                lambda: jnp.zeros((B,), bool),
            )
            new_blocks, pres_packed = sweep_insert(
                blocks, upd, starts,
                R=R, KMAX=KMAX, interpret=interp, with_presence=True,
            )
        with jax.named_scope(UNSORT_SCOPE):
            v = pres_packed.reshape(P, 8, KMAX // 8).transpose(0, 2, 1)
            v = v.reshape(-1)
            # single-column unsort: key = (idx+1) << 1 | hit sorts by
            # original index with the verdict riding the LSB; filler
            # slots (v == 0) map to the max key and sink to the tail
            vkey = jnp.where(
                v == 0,
                _u32(0xFFFFFFFE),  # even: filler slots must read as hit=0
                ((v & _u32(0x7FFFFFFF)) << _u32(1)) | (v >> _u32(31)),
            )
            (skey,) = lax.sort((vkey,), num_keys=1)
            fused = (skey[:B] & _u32(1)) == 1
            present = jnp.where(overflow, presence_fb, fused)
        if storage_fat:
            new_blocks = new_blocks.reshape(fat_shape)
        return new_blocks, present

    return insert


# =========================================================================
# Read-only fat query sweep — the dedicated query kernel (ISSUE 12)
# =========================================================================
#
# Why a query kernel at all: RESULTS_r5 §4 fenced every GATHER-based
# query at ~60M keys/s (XLA's row gather serves one row per ~12.3 ns
# regardless of locality) and measured the full gather query at 41.7M
# (BENCH r05 query_only) — the read path is now the slow half of the
# device-speed gap (insert-only runs 67.7M). §4 also argued a sweep
# query "would be a wash" against the FUSED kernel's front-end — but
# that arithmetic charged the query the fused kernel's whole budget.
# RESULTS_r5 §2 proved the sweep family is per-window-OVERHEAD-bound,
# not MXU-bound, and the fused kernel's window cost is dominated by the
# machinery a pure query never needs:
#
# * no delta: the placement cnt matmul ([KJC, R8]^T @ [KJC, W*32] int8,
#   the kernel's largest contraction), the bit-plane expansion of the
#   update stream, and the plane->word pack matmuls all vanish;
# * no write-back: blocks stream HBM->VMEM only (half the array DMA),
#   there is no donated-blocks chain, and the output is just the
#   presence tiles — so query steps need no buffer donation and can
#   pipeline against a concurrent reader;
# * no counter planes, no merge/representative selection.
#
# What remains per window is exactly the r5 extraction trick
# (RESULTS_r5 §1): one placement one-hot, ONE [KJC, R8] @ [R8, 8W] int8
# nibble-extraction matmul, the (mask & row) == mask VPU test, and the
# slot-value pack — the lightest member of the sweep family. The
# front-end (skey sort + stream build) and the unsort are shared with
# the fused kernel and already floor-proofed stage by stage (§6b).
#
# Geometry: the scoped-VMEM update/delta buffers are gone, so query
# tiles can run LARGER lambda than presence tiles at equal footprint
# (choose_fat_query_params relaxes the scoped estimate accordingly).
# There is no hardware-validated signature set yet — every geometry
# probe-compiles through the PR-11 machinery (AOT, per-process cache +
# per-device-kind persistent disk cache), so an unvalidated shape
# demotes to the gather path instead of erroring at first use.
# benchmarks/profile_query.py is the per-stage harness;
# benchmarks/query_load.py asserts path selection + bit-exactness and
# gates the served (coalesced) read path.


def _fat_query_kernel(
    starts_ref,  # SMEM [J * P8 + 1] i32 (scalar prefetch)
    upd_ref,  # ANY [BtotP, 128]: PACK queries/row — skey, masks, idx+1
    blocks_ref,  # VMEM [S * R8, 128] fat rows (auto-streamed, read-only)
    pres_ref,  # VMEM [KJC, 128] presence tile for this grid step
    sup_ref,  # VMEM scratch [2, J, KBJP, 128] u32
    sems,  # DMA sems [2, J]
    *,
    R8: int,
    S: int,
    KJ: int,
    KBJ: int,
    P8: int,
    W: int,
    J: int,
    NBJ: int,
    PACK: int = 1,
):
    """Membership-only fat sweep: the :func:`_fat_kernel` presence half
    with the whole update/delta machinery deleted. Same substream-sorted
    stream layout (col 0 skey, 1..W mask words, W+1 idx+1), same
    double-buffered window fetches, same slot-tile output consumed by
    :func:`_fat_unsort_presence` — but ``blocks_ref`` is never written
    (no ``input_output_aliases``, no donation) and the only output is
    the presence tiles. Like the fat insert kernel there is NO in-kernel
    chunk loop: window overflow (adversarial duplicate skew) is detected
    host-side and the whole batch takes the gather fallback."""
    p = pl.program_id(0)
    num_p = pl.num_programs(0)
    STRIDE = 128 // PACK
    KJP = _packed_rows(KJ, PACK)
    KBJP = _packed_rows(KBJ, PACK)

    def a_big(j, pp):
        return ((starts_ref[j * P8 + pp * S] // PACK) // _ALIGN) * _ALIGN

    def fetch(slot, pp):
        for j in range(J):
            pltpu.make_async_copy(
                upd_ref.at[pl.ds(a_big(j, pp), KBJP), :],
                sup_ref.at[slot, j],
                sems.at[slot, j],
            ).start()

    def wait(slot):
        for j in range(J):
            pltpu.make_async_copy(
                upd_ref.at[pl.ds(0, KBJP), :],
                sup_ref.at[slot, j],
                sems.at[slot, j],
            ).wait()

    slot = lax.rem(p, 2)

    @pl.when(p == 0)
    def _():
        fetch(0, 0)

    @pl.when(p + 1 < num_p)
    def _():
        fetch(1 - slot, p + 1)

    wait(slot)
    # presence slots in a [KJC, 128] tile per grid step, slot (u, packed
    # row r) of window (j, t) at row u*KJP + r, column t*J + j — the
    # exact layout _fat_kernel emits, so the unsort is shared verbatim
    pres_acc = jnp.zeros((PACK * KJP, 128), jnp.uint32)
    colsR = lax.broadcasted_iota(jnp.int32, (KJP, R8), 1)
    colpu = lax.broadcasted_iota(jnp.int32, (KJP, 128), 1)
    iota_r = lax.broadcasted_iota(jnp.int32, (KJP, 1), 0)
    for t in range(S):
        sl = pl.ds(t * R8, R8)
        tile = blocks_ref[sl, :]  # [R8, 128] fat rows (never written)
        base_rf = (p * S + t) * R8
        for j in range(J):
            qi = j * P8 + p * S + t
            skey0 = _u32(j * NBJ) + _u32(base_rf)
            rel = ((starts_ref[qi] // PACK) // _ALIGN) * _ALIGN - a_big(j, p)
            rel = jnp.clip(rel, 0, KBJP - KJP)
            sub0 = sup_ref[slot, j, pl.ds(rel, KJP), :]  # [KJP, 128]
            a0 = a_big(j, p) + rel  # packed-row units
            end = starts_ref[qi + 1]
            # per-slot COMPUTED one-hots concat along the contraction
            # axis (raw lane slices cannot sublane-concat in Mosaic,
            # computed values can — the _fat_kernel pattern)
            ohs = []
            for u in range(PACK):
                base = u * STRIDE
                rl = (sub0[:, base : base + 1] - skey0).astype(jnp.int32)
                ohs.append(
                    jnp.where(rl == colsR, jnp.float32(1), jnp.float32(0))
                )
            oh_f32 = (
                jnp.concatenate(ohs, axis=0) if PACK > 1 else ohs[0]
            )  # [KJC, R8]
            # membership by OLD-ROW NIBBLE EXTRACTION (RESULTS_r5 §1):
            # recover each slot's block row nibble-exact through the
            # placement one-hot (int8 matmul, one-hot x values <= 15,
            # i32 accumulation), then test (mask & row) == mask on the
            # nibble planes. Slots whose row is outside this window
            # extract row 0 garbage; `real` masks them below.
            tj = tile[:, j * W : (j + 1) * W]  # [R8, W] u32
            tn = jnp.concatenate(
                [
                    ((tj >> _u32(4 * n)) & _u32(15)).astype(jnp.int8)
                    for n in range(8)
                ],
                axis=1,
            )  # [R8, 8W] row nibbles
            rn = lax.dot_general(
                oh_f32.astype(jnp.int8), tn, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )  # [KJC, 8W] per-slot row nibbles (one-hot-exact)
            rn_u = rn.astype(jnp.uint32)
            mns = []
            for u in range(PACK):
                mu = sub0[:, u * STRIDE + 1 : u * STRIDE + 1 + W]
                mns.append(
                    jnp.concatenate(
                        [(mu >> _u32(4 * n)) & _u32(15) for n in range(8)],
                        axis=1,
                    )
                )
            mn = jnp.concatenate(mns, axis=0) if PACK > 1 else mns[0]
            okf = jnp.where(
                (mn & rn_u) == mn, jnp.float32(1), jnp.float32(0)
            )
            hit = jnp.min(okf, axis=1, keepdims=True)  # [KJC, 1] f32
            vus = []
            for u in range(PACK):
                hit_u = lax.slice_in_dim(hit, u * KJP, (u + 1) * KJP, axis=0)
                idxp1 = sub0[
                    :, u * STRIDE + W + 1 : u * STRIDE + W + 2
                ]  # [KJP, 1]
                ipos = (a0 + iota_r) * PACK + u
                real = (ipos >= starts_ref[qi]) & (ipos < end) & (idxp1 > 0)
                hbit = jnp.where(hit_u > 0.5, _u32(0x80000000), _u32(0))
                v = jnp.where(real, idxp1 | hbit, _u32(0))
                vus.append(jnp.where(colpu == t * J + j, v, _u32(0)))
            v128 = (
                jnp.concatenate(vus, axis=0) if PACK > 1 else vus[0]
            )  # [KJC, 128], u-major
            pres_acc = pres_acc | v128
    pres_ref[:] = pres_acc


def fat_sweep_query(
    blocks_fat: jnp.ndarray,
    upd: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    J: int,
    R8: int,
    S: int,
    KJ: int,
    KBJ: int,
    W: int,
    interpret: bool = False,
    pack: int = 1,
) -> jnp.ndarray:
    """Run the read-only query sweep over the fat block view.

    Same stream contract as :func:`fat_sweep_insert` with presence
    (col 0 skey, 1..W masks, W+1 original index + 1, sentinel tail
    padding); returns ONLY the ``uint32[P*KJC, 128]`` presence slot
    tiles (``idx+1 | hit << 31`` per slot — the
    :func:`_fat_unsort_presence` layout). ``blocks_fat`` is read-only:
    no aliasing, no donation — a query step never invalidates the
    array a concurrent launch may also be reading."""
    NB8, L = blocks_fat.shape
    assert L == 128
    P8 = NB8 // R8
    P = P8 // S
    kjc = pack * _packed_rows(KJ, pack)
    kbjp = _packed_rows(KBJ, pack)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((S * R8, 128), lambda p, *_: (p, 0)),
        ],
        out_specs=pl.BlockSpec((kjc, 128), lambda p, *_: (p, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, J, kbjp, 128), jnp.uint32),
            pltpu.SemaphoreType.DMA((2, J)),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(
            _fat_query_kernel,
            R8=R8, S=S, KJ=KJ, KBJ=KBJ, P8=P8, W=W, J=J, NBJ=NB8,
            PACK=pack,
        ),
        out_shape=jax.ShapeDtypeStruct((P * kjc, 128), jnp.uint32),
        grid_spec=grid_spec,
        interpret=interpret,
    )
    return fn(starts, upd, blocks_fat)


def choose_fat_query_params(nb: int, batch: int, words_per_block: int = 16):
    """(J, R8, S, KJ, KBJ) for the read-only query sweep, or None.

    The query chooser entry (ISSUE 12): windows run 6-sigma slack like
    presence windows (overflow falls back to the gather query, which is
    also the universal fallback path), lambda prefers the LARGEST
    feasible value (the kernel is per-window-overhead-bound and a pure
    query has even less per-window arithmetic to amortize than the
    fused kernel — RESULTS_r5 §2/§2b), and the scoped-VMEM estimate
    drops the fused kernel's output-tile and delta terms, which is what
    lets query geometries run larger lambda at equal footprint. The
    bodies/volume caps start at the presence kernel's measured envelope
    (the query body is a strict subset of the presence body's scoped
    surfaces, so every shape the presence caps admit is safe here);
    shapes beyond it are admitted solely by the probe compile — ground
    truth on hardware, cached per process and per device kind on disk
    (the PR-11 machinery)."""
    import math

    w = words_per_block
    if 1 + w + 1 > 128:
        # stream row holds skey + W mask words + key idx in 128 lanes
        return None
    J = 128 // w
    if J < 1 or w * J != 128 or nb % J:
        return None
    NBJ = nb // J
    cap = 1024
    candidates = []
    for r8 in (32, 64, 128, 256, 512, 1024):
        if r8 > NBJ or NBJ % r8:
            continue
        lam = batch * r8 // nb
        if lam < 8:
            # the sweep streams the WHOLE array per call — a sparse
            # batch pays the full stream for a handful of rows (same
            # break-even guard as the insert choosers)
            continue
        candidates.append((-lam, r8, lam))
    for _, R8, lam in sorted(candidates):
        kj_raw = max(
            16, (lam + max(16, int(6 * math.sqrt(lam))) + 7) // 8 * 8
        )
        if kj_raw > 1024:
            continue
        KJ = kj_raw
        P8 = NBJ // R8
        for s in (8, 4, 2, 1):
            if P8 % s or s * R8 > cap or P8 // s < 2:
                continue
            pk = fat_pack(w, True)  # stream carries the idx column
            bodies = s * J * pk
            # presence-kernel caps as the floor envelope (see docstring);
            # the joint rule mirrors choose_fat_params' presence matrix
            if bodies > 128:
                continue
            volume = bodies * _packed_rows(KJ, pk) * R8
            cap_v = 3_500_000 if bodies <= 64 else 2_200_000
            if volume > cap_v:
                continue
            kbj = ((lam * s + KJ + 64 + 7) // 8) * 8
            sup_rows = _packed_rows(kbj, pk)
            kjc = pk * _packed_rows(KJ, pk)
            # scoped-VMEM estimate: double-buffered window fetches + the
            # read-only block tile + the presence tile — the fused
            # kernel's 4x (in+out tile) term shrinks to in-tile + pres
            if (
                2 * J * sup_rows * 128 * 4
                + 2 * (s * R8 * 128 * 4)
                + kjc * 128 * 4
                <= 9 * 1024 * 1024
            ):
                geom = (J, R8, s, KJ, kbj)
                if not _fat_geometry_compiles(
                    nb, w, geom, presence=False, counting=False,
                    query=True, batch=batch,
                ):
                    continue
                return geom
    return None


def auto_query_path(
    backend: str, n_blocks: int, batch: int, words_per_block: int = 16
) -> str:
    """The implementation ``query_path="auto"`` resolves to — the single
    source of truth shared by :func:`tpubloom.filter.make_blocked_query_fn`,
    the sharded per-device query loop, and the benchmarks' metadata. The
    Mosaic kernel only lowers on TPU; every other backend takes the
    gather path."""
    if backend == "tpu" and choose_fat_query_params(
        n_blocks, batch, words_per_block
    ) is not None:
        return "sweep"
    return "gather"


def resolve_query_path(
    config, batch: int, backend: str | None = None, *,
    n_blocks: int | None = None,
) -> str:
    """Resolve ``config.query_path`` ("auto"/"sweep"/"gather") for a
    batch size on the current (or given) backend — the ONE funnel for
    every blocked-membership path decision (single-chip, packed, and —
    via ``n_blocks``, which the sharded per-device loop uses to pass
    its LOCAL row count — the shard_map path)."""
    qp = getattr(config, "query_path", "auto")
    if qp != "auto":
        return qp
    if backend is None:
        backend = jax.default_backend()
    return auto_query_path(
        backend,
        config.n_blocks if n_blocks is None else n_blocks,
        batch,
        config.words_per_block,
    )


def effective_query_path(
    config, batch: int, backend: str | None = None, *,
    n_blocks: int | None = None,
) -> str:
    """:func:`resolve_query_path` with applicability folded in — what
    actually LAUNCHES. A forced ``query_path="sweep"`` on a shape the
    kernel cannot take (tiny batch below the lambda floor, odd
    geometry, every candidate probe-demoted) answers "gather" instead
    of erroring: queries are bit-identical on either path, so unlike a
    forced insert sweep there is no silent-wrong-result risk a hard
    error would protect against — and a served filter sees arbitrary
    request sizes, where erroring on small batches would make the knob
    unusable. The ``query_gather_launches`` counter reports the
    demotion. Callers that want the raw kernel contract (tests, the
    probes) use :func:`make_sweep_query_fn` directly, which still
    raises on unsupported shapes."""
    if backend is None:
        backend = jax.default_backend()
    return _effective_query_path_cached(
        getattr(config, "query_path", "auto"),
        config.n_blocks if n_blocks is None else n_blocks,
        config.words_per_block,
        batch,
        backend,
    )


@functools.lru_cache(maxsize=512)
def _effective_query_path_cached(
    query_path: str, n_blocks: int, words_per_block: int, batch: int,
    backend: str,
) -> str:
    """One chooser pass per distinct decision input, memoized — the
    launch-mix counter calls this per query launch, and the chooser's
    candidate scan (plus probe-cache lookups on TPU) is pure in these
    five values for the life of the process (probe results only ever
    warm monotonically, and the first chooser call settles them)."""
    if query_path == "gather":
        return "gather"
    if query_path == "auto" and backend != "tpu":
        return "gather"
    if choose_fat_query_params(n_blocks, batch, words_per_block) is None:
        return "gather"
    return "sweep"


def apply_fat_query(
    blocks: jnp.ndarray,
    blk: jnp.ndarray,
    bit: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    block_bits: int,
    params,
    interpret: bool | None = None,
    storage_fat: bool = False,
) -> jnp.ndarray:
    """Membership of each valid key via the read-only query sweep;
    ``params`` from :func:`choose_fat_query_params`. Returns ``bool[B]``
    (False at invalid entries). ``blocks`` is NEVER modified.

    Contract (same as the fused presence path): invalid entries
    (``valid`` False) must form a TAIL SUFFIX of the batch — they emit
    no presence slot, so a mid-batch invalid entry would shift every
    later key's verdict in the index-sorted unsort.
    ``tpubloom.filter._pack_padded`` guarantees tail padding; the
    sharded per-device loop passes ``lengths >= 0`` (NOT ``owned``) for
    exactly this reason and masks unowned verdicts after the psum.

    Windows that overflow their KJ fetch (adversarial duplicate skew)
    route the WHOLE batch to the gather query under ``lax.cond`` — the
    same correctness-safe fallback design as :func:`apply_fat_updates`.
    """
    w = block_bits // 32
    J0, R8, S, KJ, KBJ = params
    nb = blocks.size // w
    B = blk.shape[0]
    J = J0
    NBJ = nb // J
    P8 = NBJ // R8
    interp = jax.default_backend() == "cpu" if interpret is None else interpret
    pack = fat_pack(w, True)
    with jax.named_scope(SORT_SCOPE):
        blkv = jnp.where(valid, blk, nb)
        j_of = (blkv % J).astype(jnp.uint32)
        rf_of = (blkv // J).astype(jnp.uint32)
        skey = jnp.where(valid, j_of * NBJ + rf_of, _u32(J * NBJ))
        cols, nbits, packed = _pack_positions(bit, block_bits, bit.shape[-1])
        idx0 = jnp.arange(1, B + 1, dtype=jnp.uint32)  # 0 = empty slot
        sorted_cols = lax.sort((skey,) + cols + (idx0,), num_keys=1)
        ss = sorted_cols[0]
        bit_sorted = _unpack_positions(
            sorted_cols[1:-1], block_bits, bit.shape[-1], nbits, packed
        )
        masks = blocked.build_masks(bit_sorted, w)
        idx_sorted = sorted_cols[-1]
        upd, starts = _fat_stream(
            ss, masks, idx_sorted, J=J, NBJ=NBJ, P8=P8, R8=R8, KBJ=KBJ, W=w,
            pack=pack,
        )
        overflow = _fat_window_overflow(
            starts, J=J, P8=P8, S=S, KJ=KJ, KBJ=KBJ, pack=pack
        )

    def sweep_branch(ops):
        bl, u, st = ops
        with jax.named_scope(SWEEP_SCOPE):
            presb = fat_sweep_query(
                bl if storage_fat else bl.reshape(NBJ, 128), u, st,
                J=J, R8=R8, S=S, KJ=KJ, KBJ=KBJ, W=w, interpret=interp,
                pack=pack,
            )
        with jax.named_scope(UNSORT_SCOPE):
            return _fat_unsort_presence(
                presb, st, B, J=J, NBJ=NBJ, P8=P8, R8=R8, S=S,
                KJ=pack * _packed_rows(KJ, pack), KBJ=KBJ,
            )

    @jax.named_scope(SWEEP_SCOPE)
    def gather_branch(ops):
        bl, u, st = ops
        masks_orig = blocked.build_masks(bit, w)
        if storage_fat:
            hit = blocked.fat_blocked_query(bl, blk, masks_orig)
        else:
            rows = bl[jnp.minimum(jnp.where(valid, blk, 0), nb - 1)]
            hit = jnp.all((rows & masks_orig) == masks_orig, axis=-1)
        return hit & valid

    return lax.cond(overflow, gather_branch, sweep_branch, (blocks, upd, starts))


def make_sweep_query_fn(
    config, *, interpret: bool | None = None, storage_fat: bool = False,
):
    """Pure ``(blocks, keys_u8, lengths) -> bool[B]`` blocked membership
    via the read-only query sweep. Bit-identical verdicts to
    :func:`tpubloom.filter.make_blocked_query_fn`'s gather path (same
    blocked position spec; the CPU oracle is the shared ground truth).

    ``storage_fat``: blocks are the fat [NB/J, 128] view (the
    persistent-filter layout — no reshape at the kernel boundary).
    Requires batch padding (lengths < 0) at the TAIL of the batch
    (tpubloom.filter._pack_padded guarantees this); padded entries
    return False.
    """
    nb, bb, w = config.n_blocks, config.block_bits, config.words_per_block
    k, seed, bh = config.k, config.seed, config.block_hash

    def query(blocks, keys_u8, lengths):
        B = keys_u8.shape[0]
        params = choose_fat_query_params(nb, B, w)
        if params is None:
            raise ValueError(
                f"sweep query does not support this shape (n_blocks={nb}, "
                f"batch={B}, words_per_block={w}) — use query_path='gather'"
            )
        valid = lengths >= 0
        blk, bit = blocked.block_positions(
            keys_u8, jnp.maximum(lengths, 0),
            n_blocks=nb, block_bits=bb, k=k, seed=seed, block_hash=bh,
        )
        return apply_fat_query(
            blocks, blk, bit, valid,
            block_bits=bb, params=params, interpret=interpret,
            storage_fat=storage_fat,
        )

    return query
