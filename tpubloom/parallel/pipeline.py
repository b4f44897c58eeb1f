"""Streaming insert pipeline — BASELINE config 3 (1B-key streams).

Parity: the reference has no streaming story; its closest tool is Redis
pipelining of per-key commands (SURVEY.md §2.2 "Streaming/pipeline
parallel"). The TPU-native equivalent pinned there: a host->device input
pipeline for billion-key streams with periodic checkpoint overlap.

Mechanics:

* the host packs fixed-size key batches while the device crunches the
  previous ones — JAX's async dispatch IS the double buffer; the pipeline
  just avoids synchronizing, with a bounded in-flight window as
  backpressure so host-side buffers can't pile up;
* every ``checkpoint_every`` keys the AsyncCheckpointer snapshots the array
  (HBM copy + async D2H + background write) WITHOUT stalling inserts, and
  records the stream offset in the checkpoint header;
* **crash recovery contract** (SURVEY.md §5 failure row): on restart,
  ``resume_offset`` says where the newest checkpoint cut the stream.
  Replaying the source from any point <= that offset is safe — scatter-OR
  is idempotent, so at-least-once delivery converges to the same bits —
  and everything before the offset is guaranteed present. Tail loss is
  bounded by ``checkpoint_every`` + one in-flight batch window.
"""

from __future__ import annotations

import queue
import threading
from typing import Iterable, Iterator, Optional

import numpy as np

from tpubloom.checkpoint import AsyncCheckpointer
from tpubloom.utils.packing import pack_keys


class StreamInserter:
    """Feed an unbounded key stream into a filter at full device rate.

    ``prefetch > 0`` overlaps host packing + H2D staging with device
    compute: a background thread packs the NEXT ``prefetch`` batches and
    starts their transfers while the device crunches the current one
    (the host's pack loop and the H2D latency otherwise
    serialize with every insert dispatch)."""

    def __init__(
        self,
        filter_obj,
        *,
        batch_size: int = 1 << 16,
        sink=None,
        checkpoint_every: int = 0,
        max_in_flight: int = 8,
        start_offset: int = 0,
        prefetch: int = 0,
    ):
        self.filter = filter_obj
        self.batch_size = batch_size
        self.max_in_flight = max_in_flight
        self.prefetch = prefetch
        self.consumed = start_offset  # keys consumed from the stream origin
        self._dispatched_since_sync = 0
        self.checkpointer: Optional[AsyncCheckpointer] = None
        if sink is not None and checkpoint_every:
            # meta_fn snapshots the offset at trigger time, under the same
            # control flow as inserts (run() is single-threaded), so the
            # recorded offset is consistent with the snapshotted bits.
            self.checkpointer = AsyncCheckpointer(
                filter_obj,
                sink,
                every_n_inserts=checkpoint_every,
                meta_fn=lambda: {"stream_offset": self._synced_offset()},
            )

    def _synced_offset(self) -> int:
        """Offset fully materialized on device at snapshot time.

        Everything dispatched is captured by the snapshot: the HBM copy in
        trigger() is enqueued AFTER all pending insert kernels on the same
        device stream, so `consumed` (all keys handed to the device) is the
        safe offset.
        """
        return self.consumed

    def _packed_batches(self, it: Iterator[bytes], limit: Optional[int]):
        """Yield ``(keys_u8, lengths, n_valid)`` fixed-shape batches."""
        produced = 0
        while True:
            budget = self.batch_size
            if limit is not None:
                budget = min(budget, limit - produced)
                if budget <= 0:
                    return
            batch = []
            for key in it:
                batch.append(key)
                if len(batch) >= budget:
                    break
            if not batch:
                return
            keys_u8, lengths = pack_keys(
                batch, self.filter.config.key_len,
                key_policy=self.filter.config.key_policy,
            )
            if len(batch) < self.batch_size:  # static-shape padding
                pad = self.batch_size - len(batch)
                keys_u8 = np.pad(keys_u8, ((0, pad), (0, 0)))
                lengths = np.pad(lengths, (0, pad), constant_values=-1)
            produced += len(batch)
            yield keys_u8, lengths, len(batch)

    def _prefetched(self, batches):
        """Run the packer on a background thread; stage each batch onto
        the device (jax.device_put starts the H2D without blocking) so
        transfers overlap device compute. Exceptions re-raise in the
        consumer."""
        import jax

        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        cancel = threading.Event()
        _END, _ERR = object(), object()

        def put(item) -> bool:
            # bounded put that gives up when the consumer is gone — a
            # plain q.put could block forever on early consumer exit,
            # stalling the unwind and leaking the thread + its buffers
            while not cancel.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for keys_u8, lengths, n in batches:
                    if not put((jax.device_put(keys_u8), jax.device_put(lengths), n)):
                        return
            except BaseException as e:  # noqa: BLE001 — re-raised below
                put((_ERR, e, 0))
                return
            put((_END, None, 0))

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item[0] is _END:
                    return
                if item[0] is _ERR:
                    raise item[1]
                yield item
        finally:
            cancel.set()
            while not q.empty():  # unblock a put-in-progress
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=30)

    def run(self, keys: Iterable[bytes], *, limit: Optional[int] = None) -> dict:
        """Consume the stream (optionally at most ``limit`` keys). Returns
        run stats. Reentrant: call again to continue the same stream."""
        it: Iterator[bytes] = iter(keys)
        inserted = 0
        batches = self._packed_batches(it, limit)
        if self.prefetch:
            batches = self._prefetched(batches)
        for keys_u8, lengths, n_valid in batches:
            self.filter.insert_arrays(keys_u8, lengths, n_valid=n_valid)
            inserted += n_valid
            self.consumed += n_valid
            self._dispatched_since_sync += 1
            if self._dispatched_since_sync >= self.max_in_flight:
                # backpressure: bound the async dispatch queue
                self.filter.block_until_ready()
                self._dispatched_since_sync = 0
            if self.checkpointer:
                self.checkpointer.notify_inserts(n_valid)
        self.filter.block_until_ready()
        return {
            "inserted": inserted,
            "stream_offset": self.consumed,
            "checkpoints_written": (
                self.checkpointer.checkpoints_written if self.checkpointer else 0
            ),
        }

    def close(self, *, final_checkpoint: bool = True) -> bool:
        """Flush and stop checkpointing. Returns False when the requested
        final checkpoint did NOT land — callers using close() as the
        durability point before discarding the source stream must check it
        (``checkpointer.last_error`` has the cause). No checkpointer
        configured -> trivially True."""
        if self.checkpointer:
            return self.checkpointer.close(final_checkpoint=final_checkpoint)
        return True


def resume_offset(restored_filter) -> int:
    """Stream offset recorded in the checkpoint a filter was restored from
    (0 if none): restart the source at or before this offset and re-run —
    idempotent inserts make the replay safe."""
    meta = getattr(restored_filter, "_restored_meta", None) or {}
    return int(meta.get("stream_offset", 0))
