"""CuckooFilter — deletable membership without 4-bit counters (ISSUE 19).

Front-end class over :mod:`tpubloom.ops.cuckoo`. Storage is the flat
``uint32[m]`` slot array (m = ``config.m`` fingerprint slots, power of
two; viewed as ``[m/BUCKET_SIZE, BUCKET_SIZE]`` buckets in-kernel), so
the checkpoint / replication / migration planes move it exactly like
every other kind's flat word array.

Semantic differences from the bloom family, surfaced honestly:

* ``insert_batch`` can FAIL per key (table full after ``MAX_KICKS``
  relocations). The per-key verdicts are staged device-side and fetched
  by :meth:`take_insert_flags` — the service / coalescer call it after
  the kernel fence and ship a ``full`` bitmap in the response instead of
  silently dropping keys.
* inserts are multiset (duplicate adds store extra copies), so inserts
  AND deletes are replay-unsafe — the kind registry classifies them for
  the rid-dedup cache.
* ``delete_batch`` removes ONE stored copy per key and reports per-key
  whether a copy existed. Deleting a never-inserted key is a contract
  violation (it may evict another key's fingerprint) — same rule as
  every cuckoo filter; the flags let callers detect it.
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np

from tpubloom import faults
from tpubloom.config import FilterConfig
from tpubloom.filter import _FilterBase
from tpubloom.obs import context as obs
from tpubloom.obs import counters as obs_counters
from tpubloom.ops import cuckoo as ops_cuckoo


class CuckooFilter(_FilterBase):
    """Bucketed-fingerprint cuckoo filter on a flat uint32 device array."""

    def __init__(self, config: FilterConfig):
        if config.kind != "cuckoo":
            raise ValueError(f"CuckooFilter needs kind='cuckoo', got {config.kind!r}")
        if config.m < ops_cuckoo.BUCKET_SIZE * 2:
            raise ValueError(
                f"cuckoo needs at least 2 buckets ({2 * ops_cuckoo.BUCKET_SIZE} "
                f"slots), got m={config.m}"
            )
        super().__init__(config, config.m)
        n_buckets = config.m // ops_cuckoo.BUCKET_SIZE
        self.n_buckets = n_buckets
        seed = config.seed
        shape = (n_buckets, ops_cuckoo.BUCKET_SIZE)

        def _derive(keys_u8, lengths):
            return ops_cuckoo.derive(
                keys_u8, lengths, n_buckets=n_buckets, seed=seed
            )

        def _ins(words, keys_u8, lengths):
            valid = lengths >= 0
            fp, i1 = _derive(keys_u8, lengths)
            slots, ok, kicks = ops_cuckoo.cuckoo_insert(
                words.reshape(shape), fp, i1, valid
            )
            return slots.reshape(-1), ok, kicks.sum()

        def _qry(words, keys_u8, lengths):
            valid = lengths >= 0
            fp, i1 = _derive(keys_u8, lengths)
            return ops_cuckoo.cuckoo_query(words.reshape(shape), fp, i1, valid)

        def _del(words, keys_u8, lengths):
            valid = lengths >= 0
            fp, i1 = _derive(keys_u8, lengths)
            slots, deleted = ops_cuckoo.cuckoo_delete(
                words.reshape(shape), fp, i1, valid
            )
            return slots.reshape(-1), deleted

        self._insert_full = jax.jit(_ins, donate_argnums=0)
        self._query = jax.jit(_qry)
        self._delete = jax.jit(_del, donate_argnums=0)
        #: (device ok flags, true batch size, device kick count) of the
        #: last insert, until take_insert_flags() collects it.
        self._pending_flags = None

    # -- insert (every path funnels through launch_insert so the FULL
    # verdicts are never lost, whichever plane drove the batch) ----------

    def launch_insert(self, staged):
        d_keys, d_lengths, B = staged
        faults.fire("cuckoo.kick", filter=self.config.key_name, batch=B)
        with obs.phase("kernel"):
            self.words, ok, kicks = self._insert_full(self.words, d_keys, d_lengths)
        self._pending_flags = (ok, B, kicks)
        self.n_inserted += B
        return self.words

    def insert_batch(self, keys: Sequence[bytes | str]) -> None:
        out = self.launch_insert(self.stage_batch(keys))
        if obs.fencing():
            with obs.phase("kernel"):
                self._kernel_fence(out)

    def insert_arrays(self, keys_u8, lengths, *, n_valid=None) -> None:
        faults.fire("cuckoo.kick", filter=self.config.key_name)
        self.words, ok, kicks = self._insert_full(self.words, keys_u8, lengths)
        B = int(keys_u8.shape[0]) if n_valid is None else n_valid
        self._pending_flags = (ok, B, kicks)
        self.n_inserted += B

    def take_insert_flags(self):
        """Per-key success flags of the LAST insert (bool[B]; False ==
        FULL), or None if already collected. Also settles the kick /
        rejection counters — metrics follow the acked batch, not the
        async launch."""
        pending = self._pending_flags
        self._pending_flags = None
        if pending is None:
            return None
        ok, B, kicks = pending
        flags = np.asarray(ok)[:B]
        nk = int(np.asarray(kicks))
        if nk:
            obs_counters.incr("cuckoo_kicks_total", nk)
        rejected = int(B - flags.sum())
        if rejected:
            obs_counters.incr("cuckoo_full_rejections", rejected)
        return flags

    # -- delete ----------------------------------------------------------

    def delete_batch(self, keys: Sequence[bytes | str]) -> np.ndarray:
        """Remove one stored copy per key; returns bool[B] per-key
        "a copy existed"."""
        keys_u8, lengths, B = self._pack_padded(keys)
        d_keys, d_lengths = self._stage_batch(keys_u8, lengths)
        with obs.phase("kernel"):
            self.words, deleted = self._delete(self.words, d_keys, d_lengths)
            if obs.fencing():
                self._kernel_fence(self.words)
        with obs.phase("d2h"):
            out = np.asarray(deleted)
        return out[:B]

    # -- stats / persistence hooks --------------------------------------

    def clear(self) -> None:
        super().clear()
        self._pending_flags = None

    def fill_ratio(self) -> float:
        occ = int(
            np.asarray(
                ops_cuckoo.occupancy(
                    self.words.reshape(self.n_buckets, ops_cuckoo.BUCKET_SIZE)
                )
            )
        )
        return occ / self.config.m

    def stats(self) -> dict:
        fill = self.fill_ratio()
        return {
            "kind": "cuckoo",
            "m": self.config.m,
            "n_buckets": self.n_buckets,
            "bucket_size": ops_cuckoo.BUCKET_SIZE,
            "max_kicks": ops_cuckoo.MAX_KICKS,
            "n_inserted": self.n_inserted,
            "n_queried": self.n_queried,
            "occupied_slots": int(round(fill * self.config.m)),
            "fill_ratio": fill,
        }
