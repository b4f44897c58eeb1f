"""CountMinSketch / TopKSketch — frequency workloads (ISSUE 19).

Front-end classes over :mod:`tpubloom.ops.cms`. Storage is the flat
``uint32[depth * width]`` counter grid (``width = config.m``, ``depth =
config.k`` — the bloom geometry fields reinterpreted, so the sizing /
hashing / checkpoint plumbing carries over unchanged). ``insert_batch``
is a unit increment (what the shared coalescer / streaming planes
drive); :meth:`increment_batch` takes per-key weights (``CMSIncrBy``)
and returns the post-update estimates; ``include_batch`` answers
"estimate > 0" so the presence machinery works unmodified.

Replayed increments DOUBLE counts — the kind registry classifies cms /
topk inserts replay-unsafe, which routes them through the rid-dedup
cache (the SIGKILL acceptance's "neither lost nor doubled").

:class:`TopKSketch` adds the heavy-hitter heap: a host-side ``{key:
estimate}`` dict of at most ``config.topk`` entries, refreshed from a
device-side estimate pass after every update batch (the CMS estimate IS
the heavy-hitter score — no second sketch). The heap rides checkpoints
through the header's extra block (:meth:`sketch_extra` /
:meth:`load_sketch_extra`), hex-encoded because headers are JSON.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tpubloom import faults
from tpubloom.config import FilterConfig
from tpubloom.filter import _FilterBase
from tpubloom.obs import context as obs
from tpubloom.obs import counters as obs_counters
from tpubloom.ops import cms as ops_cms


class CountMinSketch(_FilterBase):
    """[depth, width] count-min grid on a flat uint32 device array."""

    KINDS = ("cms",)

    def __init__(self, config: FilterConfig):
        if config.kind not in self.KINDS:
            raise ValueError(
                f"{type(self).__name__} needs kind in {self.KINDS}, got {config.kind!r}"
            )
        width, depth, seed = config.m, config.k, config.seed
        super().__init__(config, width * depth)
        self.width = width
        self.depth = depth

        def _pos(keys_u8, lengths):
            return ops_cms.cms_positions(
                keys_u8, lengths, width=width, depth=depth, seed=seed
            )

        def _ins(words, keys_u8, lengths):
            valid = lengths >= 0
            ones = jnp.ones(lengths.shape, jnp.uint32)
            return ops_cms.cms_update(words, _pos(keys_u8, lengths), valid, ones)

        def _qry(words, keys_u8, lengths):
            valid = lengths >= 0
            est = ops_cms.cms_estimate(words, _pos(keys_u8, lengths), valid)
            return est > 0

        def _incr(words, keys_u8, lengths, incs):
            valid = lengths >= 0
            return ops_cms.cms_update(words, _pos(keys_u8, lengths), valid, incs)

        def _est(words, keys_u8, lengths):
            valid = lengths >= 0
            return ops_cms.cms_estimate(words, _pos(keys_u8, lengths), valid)

        self._insert = jax.jit(_ins, donate_argnums=0)
        self._query = jax.jit(_qry)
        self._incr = jax.jit(_incr, donate_argnums=0)
        self._estimate = jax.jit(_est)

    # -- update paths (all funnel through launch_insert / _apply_incr so
    # the fault point and the top-k hook see every batch) ----------------

    def launch_insert(self, staged):
        d_keys, d_lengths, B = staged
        faults.fire("cms.update", filter=self.config.key_name, batch=B)
        with obs.phase("kernel"):
            self.words = self._insert(self.words, d_keys, d_lengths)
        self.n_inserted += B
        self._post_update(d_keys, d_lengths, B)
        return self.words

    def insert_batch(self, keys: Sequence[bytes | str]) -> None:
        out = self.launch_insert(self.stage_batch(keys))
        if obs.fencing():
            with obs.phase("kernel"):
                self._kernel_fence(out)

    def insert_arrays(self, keys_u8, lengths, *, n_valid=None) -> None:
        faults.fire("cms.update", filter=self.config.key_name)
        self.words = self._insert(self.words, keys_u8, lengths)
        B = int(keys_u8.shape[0]) if n_valid is None else n_valid
        self.n_inserted += B
        self._post_update(keys_u8, lengths, B)

    def increment_batch(
        self, keys: Sequence[bytes | str], increments: Sequence[int]
    ) -> np.ndarray:
        """Weighted increment (``CMSIncrBy``); returns the POST-update
        estimates (uint32[B]) — the verb's Redis-parity response."""
        if len(increments) != len(keys):
            raise ValueError(
                f"{len(increments)} increments for {len(keys)} keys"
            )
        incs = [int(i) for i in increments]
        if any(i < 0 or i >= (1 << 32) for i in incs):
            raise ValueError("increments must be u32 (>= 0)")
        keys_u8, lengths, B = self._pack_padded(keys)
        padded = np.zeros(lengths.shape, np.uint32)
        padded[:B] = np.asarray(incs, np.uint32)
        d_keys, d_lengths = self._stage_batch(keys_u8, lengths)
        faults.fire("cms.update", filter=self.config.key_name, batch=B)
        with obs.phase("kernel"):
            self.words = self._incr(
                self.words, d_keys, d_lengths, jnp.asarray(padded)
            )
            if obs.fencing():
                self._kernel_fence(self.words)
        self.n_inserted += B
        self._post_update(d_keys, d_lengths, B)
        with obs.phase("kernel_query"):
            est = self._estimate(self.words, d_keys, d_lengths)
        with obs.phase("d2h"):
            out = np.asarray(est)
        return out[:B]

    def _post_update(self, d_keys, d_lengths, B: int) -> None:
        """Per-batch post-update hook; TopKSketch refreshes its heap."""
        obs_counters.incr("cms_keys_incremented", B)

    # -- reads -----------------------------------------------------------

    def estimate_batch(self, keys: Sequence[bytes | str]) -> np.ndarray:
        """Point estimates (``CMSQuery``): uint32[B], only ever >= truth."""
        keys_u8, lengths, B = self._pack_padded(keys)
        d_keys, d_lengths = self._stage_batch(keys_u8, lengths)
        with obs.phase("kernel_query"):
            est = self._estimate(self.words, d_keys, d_lengths)
            if obs.fencing():
                self._kernel_fence(est)
        with obs.phase("d2h"):
            out = np.asarray(est)
        self.n_queried += B
        return out[:B]

    # -- stats -----------------------------------------------------------

    def fill_ratio(self) -> float:
        """Fraction of NONZERO counters (collision-pressure signal; the
        bloom fill/FPR model doesn't apply to counter grids)."""
        nz = int(np.asarray((self.words != 0).sum()))
        return nz / (self.width * self.depth)

    def stats(self) -> dict:
        return {
            "kind": self.config.kind,
            "width": self.width,
            "depth": self.depth,
            "n_inserted": self.n_inserted,
            "n_queried": self.n_queried,
            "fill_ratio": self.fill_ratio(),
        }


class TopKSketch(CountMinSketch):
    """CMS + host-side heavy-hitter heap of the ``config.topk`` largest
    estimates seen. Updated synchronously after each batch from a
    device-side estimate pass, so TopKList is a pure host read."""

    KINDS = ("topk",)

    def __init__(self, config: FilterConfig):
        super().__init__(config)
        self._heap: dict[bytes, int] = {}

    def _post_update(self, d_keys, d_lengths, B: int) -> None:
        super()._post_update(d_keys, d_lengths, B)
        if not B:
            return
        with obs.phase("kernel_query"):
            est = self._estimate(self.words, d_keys, d_lengths)
        with obs.phase("d2h"):
            est_np = np.asarray(est)
            rows = np.asarray(d_keys)
            lens = np.asarray(d_lengths)
        heap, cap = self._heap, self.config.topk
        for i in range(B):
            key = rows[i, : lens[i]].tobytes()
            count = int(est_np[i])
            if key in heap:
                heap[key] = max(heap[key], count)
            elif len(heap) < cap:
                heap[key] = count
            else:
                smallest = min(heap, key=heap.get)
                if count > heap[smallest]:
                    del heap[smallest]
                    heap[key] = count
        obs_counters.incr("topk_heap_updates", B)

    def topk_list(self) -> list:
        """[(key bytes, estimate)] sorted by estimate desc, then key —
        deterministic so replicas/goldens agree."""
        return sorted(self._heap.items(), key=lambda kv: (-kv[1], kv[0]))

    def clear(self) -> None:
        super().clear()
        self._heap = {}

    # -- checkpoint extra block ------------------------------------------

    def sketch_extra(self) -> dict:
        return {
            "topk_heap": [[k.hex(), c] for k, c in self.topk_list()]
        }

    def load_sketch_extra(self, extra: dict) -> None:
        heap = (extra or {}).get("topk_heap") or []
        self._heap = {bytes.fromhex(k): int(c) for k, c in heap}
