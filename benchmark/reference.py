"""The plain reference, and the comparison that decides ``correct``.

The hash spec is copied from ``tpubloom/cpu_ref.py`` and
``tpubloom/utils/packing.py`` (NumPy only, no native path) so that no
later PR can move the yardstick: this file imports nothing of the
program. A blocked Bloom filter puts all k bits of a key in one block of
``block_bits``; a sharded one first routes each key to a shard with a
separately seeded hash and uses the per-shard block count.

Concurrent callers let the server pick the order in which batches
apply, so the comparison holds each verdict to the only two things the
order the server acknowledged pins down. Bloom filters only ever set
bits, and a key is present when all of its k bits are. With ``s`` and
``a`` the send and ack times of RPC ``r`` on the shared monotonic clock:

- *must be present*: every bit of the key was set by an insert RPC
  acknowledged before ``r`` was sent (the guarantee every configuration
  states: an acknowledged insert is seen by every later query);
- *may be present*: every bit was set by some insert RPC other than
  ``r`` that was sent before ``r`` was acknowledged (presence reports the
  state before the RPC's own batch).

A verdict outside those bounds is wrong. The check runs on a sample of
verdicts drawn from the seed (``check_sample`` in the mix) and, for each
sampled key, on every inserted key that shares its block, so it is
exact for the keys it samples. Finding those inserted keys means
regenerating and hashing every key an insert RPC carried, which grows
with the keys served; that pass runs on one worker process per usable
host core, over contiguous shards of the insert RPCs.
"""

from __future__ import annotations

import functools
import hashlib
import json
import multiprocessing
import os
import tempfile
from concurrent.futures import ProcessPoolExecutor

import numpy as np

import traffic

SEED_XOR_HB = 0x9E3779B9
SEED_XOR_GB = 0x85EBCA6B
SEED_XOR_ROUTE = 0x517CC1B7
_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
CHUNK_KEYS = 1 << 20  # inserted keys hashed per step of the check
MASK_BITS = 1 << 29  # the check's prefilter of bit ids: 64 MiB
COLUMNS = ("stream", "i", "n", "rpc", "send", "ack")  # of the insert RPCs, as the check's workers get them


def _rotl32(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def murmur3_32(keys: np.ndarray, lengths: np.ndarray, seed: int) -> np.ndarray:
    """MurmurHash3_x86_32 of zero-padded keys ``uint8[B, L]``."""
    lengths = np.asarray(lengths, np.int32)
    keys = np.asarray(keys, np.uint8)
    if keys.shape[1] % 4 == 0:
        blocks = np.ascontiguousarray(keys).view("<u4").astype(np.uint32)
    else:
        kb = keys.astype(np.uint32)
        blocks = kb[:, 0::4] | (kb[:, 1::4] << 8) | (kb[:, 2::4] << 16) | (kb[:, 3::4] << 24)
    shortest = int(lengths.min()) if len(lengths) else 0
    h = np.full(lengths.shape, np.uint32(seed), np.uint32)
    with np.errstate(over="ignore"):
        for i in range(keys.shape[1] // 4):
            kk = _rotl32(blocks[:, i] * _C1, 15) * _C2
            full = _rotl32(h ^ kk, 13) * np.uint32(5) + np.uint32(0xE6546B64)
            if shortest >= 4 * (i + 1):  # every key fills this word
                h = full
                continue
            rem = lengths - 4 * i
            h = np.where(rem >= 4, full, np.where(rem > 0, h ^ kk, h))
        h = h ^ lengths.astype(np.uint32)
        h ^= h >> np.uint32(16)
        h = h * np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h = h * np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return h


def fnv1a_32(keys: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    lengths = np.asarray(lengths, np.int32)
    kb = np.asarray(keys, np.uint8).astype(np.uint32)
    h = np.full(lengths.shape, np.uint32(0x811C9DC5), np.uint32)
    with np.errstate(over="ignore"):
        for j in range(keys.shape[1]):
            h = np.where(j < lengths, (h ^ kb[:, j]) * np.uint32(0x01000193), h)
    return h


def pack(keys, key_len: int, key_policy: str) -> tuple[np.ndarray, np.ndarray]:
    """Keys (a ``traffic.Keys``) as zero-padded ``uint8[B, key_len]`` and
    lengths; a key over ``key_len`` becomes its 16-byte BLAKE2b digest
    under ``key_policy == "digest"``."""
    if keys.fixed is not None:
        w = keys.fixed.shape[1]
        if w > key_len:
            raise ValueError(f"{w}-byte keys over key_len {key_len}")
        out = np.zeros((len(keys.fixed), key_len), np.uint8)
        out[:, :w] = keys.fixed
        return out, np.full(len(out), w, np.int32)
    off = keys.offsets
    lens = np.diff(off).astype(np.int32)
    out = np.zeros((len(lens), key_len), np.uint8)
    short = lens <= key_len
    rows = np.repeat(np.flatnonzero(short), lens[short])
    starts = np.repeat(off[:-1][short], lens[short])
    cols = np.arange(len(rows)) - np.repeat(np.cumsum(lens[short]) - lens[short], lens[short])
    out[rows, cols] = keys.data[starts + cols]
    long_ = np.flatnonzero(~short)
    if len(long_):
        if key_policy != "digest":
            raise ValueError("key over key_len without key_policy=digest")
        raw = keys.data.tobytes()
        for r in long_:
            d = hashlib.blake2b(raw[off[r]:off[r + 1]], digest_size=16).digest()
            out[r, :16] = np.frombuffer(d, np.uint8)
        lens = lens.copy()
        lens[long_] = 16
    return out, lens


class Spec:
    """The position spec of one filter configuration."""

    def __init__(self, cfg: dict):
        self.cfg = dict(cfg)
        self.k, self.seed = int(cfg["k"]), int(cfg["seed"])
        self.key_len, self.key_policy = int(cfg["key_len"]), cfg.get("key_policy", "error")
        self.block_bits, self.shards = int(cfg["block_bits"]), int(cfg.get("shards", 1))
        self.block_hash = cfg["block_hash"]
        self.n_blocks = int(cfg["m"]) // self.block_bits // self.shards  # per shard
        if self.block_hash not in ("chunk", "ap"):
            raise ValueError("the reference covers the blocked layouts")

    def block(self, ku8, lens) -> np.ndarray:
        """Global block id (shard-major) of each key."""
        blk = (murmur3_32(ku8, lens, self.seed) & np.uint32(self.n_blocks - 1)).astype(np.int64)
        if self.shards > 1:
            route = murmur3_32(ku8, lens, self.seed ^ SEED_XOR_ROUTE) % np.uint32(self.shards)
            blk += route.astype(np.int64) * self.n_blocks
        return blk

    def bits(self, ku8, lens) -> np.ndarray:
        """In-block bit positions ``int64[B, k]``."""
        g_a = fnv1a_32(ku8, lens)
        g_b = murmur3_32(ku8, lens, self.seed ^ SEED_XOR_GB)
        mask = np.uint32(self.block_bits - 1)
        if self.block_hash == "ap":
            i = np.arange(self.k, dtype=np.uint32)
            with np.errstate(over="ignore"):
                return ((g_a[:, None] + i * (g_b | np.uint32(1))[:, None]) & mask).astype(np.int64)
        nb = (self.block_bits - 1).bit_length()
        pool = (murmur3_32(ku8, lens, self.seed ^ SEED_XOR_HB), g_a, g_b)
        cols = []
        for i in range(self.k):
            w, off = (i * nb) >> 5, (i * nb) & 31
            v = pool[w] >> np.uint32(off)
            if off + nb > 32:
                v = v | (pool[w + 1] << np.uint32(32 - off))
            cols.append(v & mask)
        return np.stack(cols, axis=1).astype(np.int64)

    def positions(self, ku8, lens) -> np.ndarray:
        """Global bit ids ``int64[B, k]``."""
        return self.block(ku8, lens)[:, None] * self.block_bits + self.bits(ku8, lens)


class _IdSet:
    """Sorted bit ids, with a bitmap of them modulo MASK_BITS: most other
    bits are dropped by it before the exact search."""

    def __init__(self, ids: np.ndarray):
        self.ids = ids
        low = ids & (MASK_BITS - 1)
        self.mask = np.zeros(MASK_BITS // 8, np.uint8)
        np.bitwise_or.at(self.mask, low >> 3, (1 << (low & 7)).astype(np.uint8))

    def index(self, pos: np.ndarray) -> np.ndarray:
        """Where each of ``pos`` is in ``ids``, or -1."""
        low = pos & (MASK_BITS - 1)
        hit = np.flatnonzero((self.mask[low >> 3] >> (low & 7).astype(np.uint8)) & 1)
        g = np.minimum(np.searchsorted(self.ids, pos[hit]), len(self.ids) - 1)
        out = np.full(len(pos), -1, np.int64)
        out[hit] = np.where(self.ids[g] == pos[hit], g, -1)
        return out


class _Earliest:
    """Per bit id of interest (``ids``, sorted): the earliest ack of an
    insert RPC that set it, and the two earliest sends from different
    RPCs, so that one RPC's own insert can be left out. What it holds
    does not depend on how the inserts are split among calls to ``add``
    or in which order they come."""

    def __init__(self, ids: np.ndarray):
        self.ids = ids
        n = len(ids)
        self.ack = np.full(n, np.inf)
        self.send1, self.send2 = np.full(n, np.inf), np.full(n, np.inf)
        self.rpc1 = np.full(n, -1, np.int64)

    def add(self, g, ack, s1, r1, s2) -> None:
        """Bits ``ids[g]``, each set by inserts that were acknowledged at
        the earliest at ``ack``, first sent at ``s1`` by RPC ``r1``, and
        sent at ``s2`` at the earliest by another RPC (``inf`` for one
        insert)."""
        if not len(g):
            return
        o = np.lexsort((s1, g))
        g, ack, s1, r1, s2 = g[o], ack[o], s1[o], r1[o], s2[o]
        first = np.r_[True, g[1:] != g[:-1]]
        starts = np.flatnonzero(first)
        grp = np.cumsum(first) - 1
        c1 = r1[starts]
        b2 = np.minimum.reduceat(np.where(r1 != c1[grp], s1, s2), starts)
        self.merge(g[starts], np.minimum.reduceat(ack, starts), s1[starts], c1, b2)

    def merge(self, g, ack, b1, c1, b2) -> None:
        """Fold in, for bits ``ids[g]`` (``g`` unique), what another
        ``_Earliest`` over the same ids holds (``held``)."""
        self.ack[g] = np.minimum(self.ack[g], ack)
        S1, R1, S2 = self.send1[g], self.rpc1[g], self.send2[g]
        held_first = S1 <= b1
        self.send2[g] = np.where(R1 == c1, np.minimum(S2, b2),
                                 np.where(held_first, np.minimum(S2, b1), np.minimum(b2, S1)))
        self.rpc1[g] = np.where(held_first, R1, c1)
        self.send1[g] = np.minimum(S1, b1)

    def held(self) -> tuple:
        """What is held for each bit that an insert set, as ``merge`` takes it."""
        g = np.flatnonzero(self.send1 < np.inf)
        return g, self.ack[g], self.send1[g], self.rpc1[g], self.send2[g]

    def lookup(self, pos: np.ndarray, rpc: np.ndarray):
        """(earliest ack, earliest send by another RPC than ``rpc``)."""
        g = np.searchsorted(self.ids, pos)
        send = np.where(self.rpc1[g] != rpc, self.send1[g], self.send2[g])
        return self.ack[g], send


def _by_key(ku8, lens, rpc, send, ack) -> tuple:
    """One entry per distinct key (its padded bytes and its length): the
    key, its earliest ack, and its two earliest sends by different RPCs,
    the first with its RPC."""
    words = np.ascontiguousarray(ku8).view(np.uint64) if ku8.shape[1] % 8 == 0 else ku8
    words = np.concatenate([words, lens.astype(words.dtype)[:, None]], axis=1)
    o = np.argsort(words[:, 0], kind="stable")
    if np.any((words[o[1:], 0] == words[o[:-1], 0]) & np.any(words[o[1:]] != words[o[:-1]], axis=1)):
        o = np.lexsort(tuple(words[:, j] for j in reversed(range(words.shape[1]))))
    ku8, lens, rpc, send, ack, words = (x[o] for x in (ku8, lens, rpc, send, ack, words))
    first = np.r_[True, np.any(words[1:] != words[:-1], axis=1)]
    starts = np.flatnonzero(first)
    grp = np.cumsum(first) - 1
    s1 = np.minimum.reduceat(send, starts)
    at = np.minimum.reduceat(np.where(send == s1[grp], np.arange(len(send)), len(send)), starts)
    r1 = rpc[at]
    s2 = np.minimum.reduceat(np.where(rpc != r1[grp], send, np.inf), starts)
    return ku8[starts], lens[starts], np.minimum.reduceat(ack, starts), s1, r1, s2


def _scan(spec: Spec, gen, blocks: np.ndarray, ids: _IdSet, cols: dict, lo: int, hi: int) -> tuple:
    """``_Earliest.held`` of the inserts of insert RPCs ``lo:hi`` alone
    (``cols``: each insert RPC's ``stream``, ``i``, ``n``, history index
    ``rpc``, ``send`` and ``ack``), hashed in chunks of about CHUNK_KEYS
    keys. Only keys in a block marked in ``blocks`` are hashed to their
    bits, once per distinct key in a chunk."""
    earliest = _Earliest(ids.ids)
    ku8s, lenss, held = [], [], 0
    for j in range(lo, hi):
        keys = gen.keys_at(int(cols["stream"][j]), int(cols["i"][j]), np.arange(cols["n"][j]))
        ku8, lens = pack(keys, spec.key_len, spec.key_policy)
        ku8s.append(ku8)
        lenss.append(lens)
        held += len(lens)
        if held < CHUNK_KEYS and j < hi - 1:
            continue
        r = np.repeat(np.arange(j + 1 - len(lenss), j + 1), [len(x) for x in lenss])
        ku8, lens = np.concatenate(ku8s), np.concatenate(lenss)
        ku8s, lenss, held = [], [], 0
        blk = spec.block(ku8, lens)
        sel = np.flatnonzero(blocks[blk])
        if not len(sel):
            continue
        r = r[sel]
        ku8, lens, a1, s1, r1, s2 = _by_key(ku8[sel], lens[sel], cols["rpc"][r], cols["send"][r], cols["ack"][r])
        g = ids.index((spec.block(ku8, lens)[:, None] * spec.block_bits + spec.bits(ku8, lens)).ravel())
        keep = g >= 0
        earliest.add(g[keep], *(np.repeat(x, spec.k)[keep] for x in (a1, s1, r1, s2)))
    return earliest.held()


@functools.lru_cache(maxsize=1)
def _load_task(path: str):
    """A worker's inputs, written once by ``check`` (spawned workers
    start from a fresh import)."""
    with np.load(path) as z:
        meta = json.loads(str(z["meta"]))
        cols = {c: z[c] for c in COLUMNS}
        blocks, ids = z["blocks"], z["ids"]
    return Spec(meta["cfg"]), traffic.Generator(meta["mix"], meta["seed"]), blocks, _IdSet(ids), cols


def _scan_shard(task):
    path, lo, hi = task
    return _scan(*_load_task(path), lo, hi)


def _shards(n: np.ndarray, parts: int) -> list:
    """``parts`` or fewer contiguous ranges of RPCs with about equal keys."""
    ends = np.cumsum(n)
    cuts = np.searchsorted(ends, ends[-1] * np.arange(1, parts) / parts) + 1
    bounds = np.unique(np.r_[0, np.minimum(cuts, len(n)), len(n)])
    return list(zip(bounds[:-1].tolist(), bounds[1:].tolist()))


def _held_by_shard(spec: Spec, gen, blocks: np.ndarray, ids: np.ndarray, cols: dict, workers: int):
    """``_scan`` of every insert RPC, in one contiguous shard per worker
    process (in this process where ``workers`` is 1), in history order."""
    count = len(cols["n"])
    if workers <= 1 or count <= 1:
        yield _scan(spec, gen, blocks, _IdSet(ids), cols, 0, count)
        return
    shards = _shards(cols["n"], workers)
    with tempfile.TemporaryDirectory(prefix="tpubloom-check-") as tmp:
        path = os.path.join(tmp, "task.npz")
        meta = json.dumps({"cfg": spec.cfg, "mix": gen.mix, "seed": gen.seed})
        np.savez(path, meta=np.array(meta), blocks=blocks, ids=ids, **cols)
        # spawn, not fork: the caller may hold threads (gRPC's among them);
        # a worker that dies breaks the pool, which raises, never hangs
        pool = ProcessPoolExecutor(len(shards), mp_context=multiprocessing.get_context("spawn"))
        try:
            yield from pool.map(_scan_shard, [(path, lo, hi) for lo, hi in shards])
        finally:
            pool.shutdown(wait=True, cancel_futures=True)


def check(spec: Spec, gen, history: list, seed: int, sample: int, workers: int | None = None) -> dict:
    """Compare a sample of the verdicts in ``history`` with the reference.

    ``history`` holds one dict per RPC: ``stream``, ``i``, ``op``, ``n``,
    ``ok``, ``send``/``ack`` (ns, shared monotonic clock) and, for RPCs
    with verdicts, ``verdict`` (bool[n]). A failed insert may have
    applied, so it counts as sent and never acknowledged. Returns the
    counts compared: ``missed`` (a key that must be present answered
    absent), ``phantom`` (a key that may not be present answered
    present) and ``sampled``. The inserted keys are hashed on
    ``workers`` processes, by default one per usable core; the counts
    do not depend on how many.
    """
    judged = [(n, h) for n, h in enumerate(history) if h["ok"] and h.get("verdict") is not None]
    sizes = np.array([len(h["verdict"]) for _, h in judged], np.int64)
    total = int(sizes.sum())
    if not total:
        return {"missed": 0, "phantom": 0, "sampled": 0}
    rng = np.random.default_rng([int(seed) & ((1 << 64) - 1), 0xC0FFEE])
    pick = np.sort(rng.choice(total, size=min(sample, total), replace=False))
    ends = np.cumsum(sizes)
    which = np.searchsorted(ends, pick, side="right")
    keys, q_rpc, q_send, q_ack, q_v = [], [], [], [], []
    rpcs, firsts = np.unique(which, return_index=True)  # ``which`` is sorted
    for r, lo, hi in zip(rpcs, firsts, np.r_[firsts[1:], len(which)]):
        hid, h = judged[r]
        idx = pick[lo:hi] - (ends[r] - sizes[r])
        keys.append(pack(gen.keys_at(h["stream"], h["i"], idx), spec.key_len, spec.key_policy))
        q_rpc.append(np.full(len(idx), hid))
        q_send.append(np.full(len(idx), h["send"], np.float64))
        q_ack.append(np.full(len(idx), h["ack"], np.float64))
        q_v.append(np.asarray(h["verdict"], bool)[idx])
    q_pos = spec.positions(np.concatenate([k for k, _ in keys]), np.concatenate([l for _, l in keys]))
    q_rpc, q_send, q_ack, q_v = map(np.concatenate, (q_rpc, q_send, q_ack, q_v))
    earliest = _Earliest(np.unique(q_pos))
    blocks = np.zeros(spec.n_blocks * spec.shards, bool)
    blocks[earliest.ids // spec.block_bits] = True

    # every bit of interest that an inserted key set, and when: each
    # shard of the insert RPCs is folded alone, then merged here
    ins = [hid for hid, h in enumerate(history) if h["op"] in traffic.INSERTING]
    cols = {c: np.array([history[j][c] for j in ins], np.int64) for c in ("stream", "i", "n")}
    cols["rpc"] = np.array(ins, np.int64)
    cols["send"] = np.array([float(history[j]["send"]) for j in ins], np.float64)
    cols["ack"] = np.array([float(history[j]["ack"]) if history[j]["ok"] else np.inf for j in ins], np.float64)
    if workers is None:
        workers = len(os.sched_getaffinity(0))
    for held in _held_by_shard(spec, gen, blocks, earliest.ids, cols, workers):
        earliest.merge(*held)
    k = q_pos.shape[1]
    acked, sent = earliest.lookup(q_pos.ravel(), np.repeat(q_rpc, k))
    must = acked.reshape(-1, k).max(axis=1) < q_send
    may = sent.reshape(-1, k).max(axis=1) <= q_ack
    return {
        "missed": int(np.sum(must & ~q_v)),
        "phantom": int(np.sum(q_v & ~may)),
        "sampled": int(len(q_v)),
    }
