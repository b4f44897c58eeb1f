"""The copied reference agrees with ``tpubloom.cpu_ref``, the ordering
check accepts every serial history and catches wrong verdicts, and it
gives the same counts however many processes hash the inserted keys."""

import json
import os

import numpy as np
import pytest

from conftest import HERE

import reference
import traffic
from tpubloom.config import FilterConfig
from tpubloom.cpu_ref import CPUBlockedBloomFilter, murmur3_32_np
from tpubloom.ops.hashing import SEED_XOR_ROUTE
from tpubloom.utils.packing import pack_keys

CFG = {"m": 1 << 20, "k": 7, "seed": 2538058380, "key_len": 16, "key_policy": "error",
       "block_bits": 512, "block_hash": "chunk"}
URL_CFG = dict(CFG, k=10, key_len=128, key_policy="digest")
MIX = {"mode": "unary", "processes": 1, "threads": 2, "batch": 3000, "pattern": ["query", "insert_presence"],
       "keys": {"dist": "uniform", "len": 16}, "repeat": {"insert_presence": 0.2}}
URL_MIX = dict(MIX, keys={"dist": "url", "median": 64, "sigma": 0.6, "min": 16, "max": 512})


def _words(spec, pos):
    """A reference bitset with ``pos`` set, and a membership test on it."""
    bits = np.zeros(spec.n_blocks * spec.shards * spec.block_bits, bool)
    bits[pos.ravel()] = True
    return bits


@pytest.mark.parametrize("cfg,mix,block_hash", [
    (CFG, MIX, "chunk"), (CFG, MIX, "ap"), (URL_CFG, URL_MIX, "chunk"),
])
def test_membership_agrees_with_cpu_ref(cfg, mix, block_hash):
    cfg = dict(cfg, block_hash=block_hash)
    gen = traffic.Generator(mix, 11)
    spec = reference.Spec(cfg)
    prog = CPUBlockedBloomFilter(FilterConfig.from_dict(cfg), use_native=False)
    ins = gen.rpc(0, 1)[1]
    probe = gen.rpc(1, 2)[1]
    prog.insert_batch(ins.as_list())
    bits = _words(spec, spec.positions(*reference.pack(ins, spec.key_len, spec.key_policy)))
    for keys in (ins, probe):
        pos = spec.positions(*reference.pack(keys, spec.key_len, spec.key_policy))
        np.testing.assert_array_equal(bits[pos].all(axis=1), prog.include_batch(keys.as_list()))


def test_pack_agrees_with_program_packing():
    keys = traffic.Generator(URL_MIX, 3).rpc(0, 0)[1]
    want = pack_keys(keys.as_list(), 128, key_policy="digest")
    got = reference.pack(keys, 128, "digest")
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("width", [4, 16, 20, 128])
@pytest.mark.parametrize("lengths", ["full", "mixed"])
def test_murmur3_agrees_with_program_hash(width, lengths):
    """Keys that all fill the width take the reference's short cut;
    keys of mixed lengths do not."""
    rng = np.random.default_rng(width)
    n = 3000
    lens = np.full(n, width, np.int32) if lengths == "full" else rng.integers(0, width + 1, n).astype(np.int32)
    ku8 = rng.integers(0, 256, (n, width), dtype=np.uint8)
    ku8[np.arange(width)[None, :] >= lens[:, None]] = 0
    for seed in (0, CFG["seed"] ^ SEED_XOR_ROUTE):
        np.testing.assert_array_equal(reference.murmur3_32(ku8, lens, seed), murmur3_32_np(ku8, lens, seed))


def test_shard_routing_agrees_with_program_hash():
    cfg = dict(CFG, m=1 << 24, shards=32)
    spec = reference.Spec(cfg)
    ku8, lens = reference.pack(traffic.Generator(MIX, 5).rpc(0, 0)[1], 16, "error")
    route = murmur3_32_np(ku8, lens, cfg["seed"] ^ SEED_XOR_ROUTE) % np.uint32(32)
    np.testing.assert_array_equal(spec.block(ku8, lens) // spec.n_blocks, route)
    # within a shard, the block is the single-filter spec at m / shards
    local = reference.Spec(dict(cfg, m=cfg["m"] // 32, shards=1))
    np.testing.assert_array_equal(spec.block(ku8, lens) % spec.n_blocks, local.block(ku8, lens))


def _serial_history(gen, cfg, n_rpcs, streams=2):
    """RPCs one after another, verdicts from the program's CPU filter."""
    prog = CPUBlockedBloomFilter(FilterConfig.from_dict(cfg), use_native=False)
    hist, t = [], 0
    for i in range(n_rpcs):
        for s in range(streams):
            op, keys = gen.rpc(s, i)
            kl = keys.as_list()
            v = prog.include_batch(kl)
            if op != "query":
                prog.insert_batch(kl)
            hist.append({"stream": s, "i": i, "op": op, "send": t, "ack": t + 5, "ok": True,
                         "n": len(kl), "verdict": v})
            t += 10
    return hist


def test_serial_history_is_correct_and_wrong_verdicts_are_caught():
    gen = traffic.Generator(MIX, 8)
    hist = _serial_history(gen, CFG, 8)
    spec = reference.Spec(CFG)
    got = reference.check(spec, gen, hist, 8, 4000)
    assert got == {"missed": 0, "phantom": 0, "sampled": 4000}
    for h in hist:
        h["verdict"] = ~h["verdict"]
    bad = reference.check(spec, gen, hist, 8, 4000)
    assert bad["missed"] > 0 and bad["phantom"] > 0


def test_overlapping_rpcs_may_see_each_other():
    """Two RPCs in flight at once: either order is accepted."""
    gen = traffic.Generator(MIX, 9)
    spec = reference.Spec(CFG)
    _, a = gen.rpc(0, 1)
    hist = [
        {"stream": 0, "i": 1, "op": "insert_presence", "send": 0, "ack": 100, "ok": True,
         "n": len(a), "verdict": np.zeros(len(a), bool)},
        # the same keys, sent while the first was in flight: present or not
        {"stream": 0, "i": 1, "op": "insert_presence", "send": 50, "ack": 150, "ok": True,
         "n": len(a), "verdict": np.ones(len(a), bool)},
    ]
    assert reference.check(spec, gen, hist, 1, 3000)["phantom"] == 0
    hist[1]["send"] = 120  # now sent after the first was acknowledged: must be present
    hist[1]["verdict"] = np.zeros(len(a), bool)
    assert reference.check(spec, gen, hist, 1, 3000)["missed"] > 0


with open(os.path.join(HERE, "data", "configs", "tiny-sharded.json")) as f:
    TINY_SHARDED = json.load(f)["filter"]
DEDUP_MIX = {"mode": "unary", "processes": 1, "threads": 1, "batch": 300, "pattern": ["insert_presence"],
             "keys": {"dist": "uniform", "len": 16}, "repeat": {"insert_presence": 0.5}, "repeat_lag": 1}


def _bitset_history(spec, gen, n_rpcs, streams, *, overlap=0, fail=(), flip=0.0, seed=0):
    """RPC ``k`` (callers in turn) is sent at ``10 k`` and acknowledged
    ``5 + overlap`` later; verdicts come from a reference bitset applied
    in send order, then a seeded share ``flip`` of them is inverted. The
    RPCs numbered in ``fail`` failed: no verdict, and (odd ones only)
    their keys were not applied."""
    bits = np.zeros(spec.n_blocks * spec.shards * spec.block_bits, bool)
    rng = np.random.default_rng(seed)
    hist = []
    for i in range(n_rpcs):
        for s in range(streams):
            k = len(hist)
            op, keys = gen.rpc(s, i)
            pos = spec.positions(*reference.pack(keys, spec.key_len, spec.key_policy))
            v = bits[pos].all(axis=1) ^ (rng.random(len(keys)) < flip)
            if op != "query" and not (k in fail and k % 2):
                bits[pos.ravel()] = True
            ok = k not in fail
            hist.append({"stream": s, "i": i, "op": op, "send": 10 * k, "ack": 10 * k + 5 + overlap,
                         "ok": ok, "n": len(keys), "verdict": v if ok else None})
    return hist


def _case(name):
    """(spec, generator, history, seed, sample) of one equivalence case."""
    if name == "serial-wrong":
        gen = traffic.Generator(MIX, 8)
        hist = _serial_history(gen, CFG, 8)
        for h in hist[::3]:
            h["verdict"] = ~h["verdict"]
        return reference.Spec(CFG), gen, hist, 8, 4000
    if name == "overlapping":
        gen, spec = traffic.Generator(MIX, 21), reference.Spec(CFG)
        return spec, gen, _bitset_history(spec, gen, 12, 2, overlap=25, flip=0.02, seed=1), 21, 3000
    if name == "repeats-across-shards":
        gen, spec = traffic.Generator(DEDUP_MIX, 2**31 + 77), reference.Spec(CFG)
        return spec, gen, _bitset_history(spec, gen, 48, 1, flip=0.01, seed=2), 2**31 + 77, 5000
    if name == "tiny-sharded":
        mix = dict(DEDUP_MIX, batch=512, repeat={"insert_presence": 0.3})
        gen, spec = traffic.Generator(mix, 5), reference.Spec(TINY_SHARDED)
        return spec, gen, _bitset_history(spec, gen, 10, 2, overlap=12, flip=0.02, seed=3), 5, 3000
    if name == "client-order":
        # as a run records it: caller by caller, with the warm-up RPCs,
        # sent first, at the end
        mix = dict(DEDUP_MIX, batch=200)
        gen, spec = traffic.Generator(mix, 31), reference.Spec(CFG)
        hist = _bitset_history(spec, gen, 12, 3, overlap=40, flip=0.02, seed=6)
        hist.sort(key=lambda h: (h["i"] == 0, h["stream"], h["i"]))
        return spec, gen, hist, 31, 3000
    if name == "failed-insert":
        mix = dict(DEDUP_MIX, repeat={"insert_presence": 0.3})
        gen, spec = traffic.Generator(mix, 13), reference.Spec(CFG)
        hist = _bitset_history(spec, gen, 10, 2, fail=(4, 9), flip=0.01, seed=4)
        for h in hist:
            if not h["ok"]:
                h["ack"] = 10**18  # never answered: the reference holds it as never acknowledged
        return spec, gen, hist, 13, 3000
    raise KeyError(name)


# what the single-process check before the worker pool returned on each case
PARENT_COUNTS = {
    "serial-wrong": {"missed": 92, "phantom": 1388, "sampled": 4000},
    "overlapping": {"missed": 3, "phantom": 74, "sampled": 3000},
    "repeats-across-shards": {"missed": 15, "phantom": 30, "sampled": 5000},
    "tiny-sharded": {"missed": 16, "phantom": 52, "sampled": 3000},
    "failed-insert": {"missed": 8, "phantom": 26, "sampled": 3000},
    "client-order": {"missed": 14, "phantom": 37, "sampled": 3000},
}


@pytest.mark.parametrize("chunk", [reference.CHUNK_KEYS, 97])
@pytest.mark.parametrize("name", sorted(PARENT_COUNTS))
def test_check_is_the_same_in_one_process_and_on_several(name, chunk, monkeypatch):
    """The inserted-key pass in-process and on three workers, with the
    keys hashed in-process in one chunk or in chunks of 97, gives the
    counts the single-process check gave."""
    monkeypatch.setattr(reference, "CHUNK_KEYS", chunk)
    spec, gen, hist, seed, sample = _case(name)
    one = reference.check(spec, gen, hist, seed, sample, workers=1)
    several = reference.check(spec, gen, hist, seed, sample, workers=3)
    assert one == several == PARENT_COUNTS[name]


@pytest.mark.parametrize("mix", [
    dict(MIX, keys={"dist": "zipf", "theta": 0.99, "items": 4096, "len": 16}),
    dict(URL_MIX, pattern=["insert", "query"], repeat={"query": 0.5}, batch=500),
])
def test_worker_count_does_not_change_the_result(mix):
    """Zipf keys (each worker rebuilds the generator's table) and long
    URL keys (digested in each worker) on 1, 2 and 5 workers."""
    cfg = URL_CFG if mix["keys"]["dist"] == "url" else CFG
    gen, spec = traffic.Generator(mix, 99), reference.Spec(cfg)
    hist = _bitset_history(spec, gen, 14, 3, overlap=15, flip=0.01, seed=5)
    got = [reference.check(spec, gen, hist, 99, 2000, workers=w) for w in (1, 2, 5)]
    assert got[0]["sampled"] == 2000 and got[0]["missed"] > 0
    assert got[0] == got[1] == got[2]


def test_shards_are_contiguous_and_cover_every_rpc():
    n = np.array([300, 5, 5, 5, 1000, 300, 300, 2, 300])
    shards = reference._shards(n, 4)
    assert shards[0][0] == 0 and shards[-1][1] == len(n)
    assert all(a[1] == b[0] for a, b in zip(shards, shards[1:]))
    assert all(lo < hi for lo, hi in shards) and len(shards) <= 4
    assert reference._shards(n[:1], 4) == [(0, 1)]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_earliest_does_not_depend_on_how_inserts_are_split(seed):
    """Single inserts (bit, RPC, send, ack) with many ties, added at once,
    in shuffled chunks, and folded apart per chunk then merged, agree
    with the minima taken by brute force."""
    rng = np.random.default_rng(seed)
    n, ids = 400, np.arange(0, 120, 4)
    g = rng.integers(0, len(ids), n)
    rpc, send = rng.integers(0, 6, n), rng.integers(0, 10, n).astype(float)
    ack = send + rng.integers(1, 5, n)
    whole, split, merged = (reference._Earliest(ids) for _ in range(3))
    whole.add(g, ack, send, rpc, np.full(n, np.inf))
    for part in np.array_split(rng.permutation(n), 7):
        split.add(g[part], ack[part], send[part], rpc[part], np.full(len(part), np.inf))
        alone = reference._Earliest(ids)
        alone.add(g[part], ack[part], send[part], rpc[part], np.full(len(part), np.inf))
        merged.merge(*alone.held())
    for r in range(7):
        pos, who = ids, np.full(len(ids), r)
        want_ack = [ack[g == b].min(initial=np.inf) for b in range(len(ids))]
        want_send = [send[(g == b) & (rpc != r)].min(initial=np.inf) for b in range(len(ids))]
        for e in (whole, split, merged):
            got_ack, got_send = e.lookup(pos, who)
            np.testing.assert_array_equal(got_ack, want_ack)
            np.testing.assert_array_equal(got_send, want_send)
