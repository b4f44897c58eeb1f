#!/usr/bin/env python
"""The five BASELINE.json benchmark configs as runnable scripts.

Usage: python benchmarks/run.py --config N [--scale F] [--platform cpu|tpu]

Each config prints one JSON line. --scale shrinks key counts (and for
device configs the filter size) so every config can be smoke-run on the
1-core CPU backend; --scale 1.0 on a real v5e chip is the acceptance
matrix (BASELINE.md). Defaults to a small scale on CPU.

| config | workload                                   | pins                         |
|--------|--------------------------------------------|------------------------------|
| 1      | 1M random 16B keys, m=10M, k=7             | CPU reference driver (C++)   |
| 2      | 100M-key URL dedup, m=2^30, k=10           | single-chip batched kernels  |
| 3      | 1B-key stream, m=2^34, periodic checkpoint | streaming + checkpoint       |
| 4      | counting insert/delete/query mix, m=2^30   | scatter-add kernel           |
| 5      | 64-shard array, m=2^36 total               | shard_map + all-reduce-OR    |
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _gen_keys(n: int, nbytes: int = 16, seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 256, size=(n, nbytes), dtype=np.uint8)
    return raw, np.full(n, nbytes, dtype=np.int32)


def config1(scale: float) -> dict:
    """CPU reference driver (the reference's :ruby-driver role, C++ hot
    path): 1M keys, m=10M, k=7 — the measured CPU baseline the TPU numbers
    are compared against."""
    import numpy as np

    from tpubloom import CPUBloomFilter, FilterConfig, native

    n = int(1_000_000 * scale)
    cfg = FilterConfig(m=10_000_000, k=7, key_len=16)
    f = CPUBloomFilter(cfg)  # auto-uses native when built
    keys_u8, lengths = _gen_keys(n)
    keys = [bytes(k) for k in keys_u8]
    t0 = time.perf_counter()
    f.insert_batch(keys)
    t_insert = time.perf_counter() - t0
    t0 = time.perf_counter()
    hits = f.include_batch(keys)
    t_query = time.perf_counter() - t0
    assert hits.all()
    return {
        "config": 1,
        "driver": "native-c++" if f.use_native else "numpy",
        "n": n,
        "insert_keys_per_sec": round(n / t_insert),
        "query_keys_per_sec": round(n / t_query),
        "combined_keys_per_sec": round(n / (t_insert + t_query)),
    }


def config2(scale: float, layout: str = "flat") -> dict:
    """URL-dedup: batched inserts then mixed-hit queries on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpubloom import BlockedBloomFilter, BloomFilter, FilterConfig

    n = int(100_000_000 * scale)
    nq = int(10_000_000 * scale)
    log2m = 30 if scale >= 0.1 else 24
    if layout == "blocked":
        cfg = FilterConfig(m=1 << log2m, k=10, key_len=16, block_bits=512)
        f = BlockedBloomFilter(cfg)
    else:
        cfg = FilterConfig(m=1 << log2m, k=10, key_len=16)
        f = BloomFilter(cfg)
    # B=1M, measured optimum at THIS shape (r5): the m=2^30 array is 8x
    # smaller than the north-star's, so whole-array-stream amortization
    # saturates by B=1M and larger batches only pay the sorts'
    # super-linear growth — B=8M measured 45.3M insert / 27.2M query
    # vs 60.7M / 33.1M at B=1M (config2_r5.json keeps the B=1M run).
    # The north-star m=2^32 shape is the opposite (b_sweep_r5.json).
    B = min(1 << 20, max(1 << 12, n // 8))
    # the whole insert stream runs inside ONE jit (lax.fori_loop over
    # device-generated batches): no per-batch eager dispatch in the
    # timed window
    from jax import lax as _lax

    full_steps, tail = divmod(n, B)
    lengths = jnp.full((B,), 16, jnp.int32)

    def _keys(seed):
        return jax.random.bits(jax.random.key(seed), (B, 16), jnp.uint8)

    # jit the loop around the PURE insert kernel
    from tpubloom.filter import (
        blocked_storage_fat,
        make_blocked_insert_fn,
        make_insert_fn as _mk_flat,
    )

    if layout == "blocked":
        pure_insert = make_blocked_insert_fn(
            cfg, storage_fat=blocked_storage_fat(cfg)
        )
    else:
        pure_insert = _mk_flat(cfg)

    def _loop(words, n_steps):
        def body(i, w):
            return pure_insert(w, _keys(i), lengths)

        return _lax.fori_loop(0, n_steps, body, words)

    loop_jit = jax.jit(_loop, static_argnums=1, donate_argnums=0)
    def _tail_insert():
        # masked tail batch (its own jit cache entry): exactly `tail`
        # real keys land, the rest carry length -1 and set no bits
        iota = jnp.arange(B, dtype=jnp.int32)
        f.insert_arrays(
            _keys(full_steps), jnp.where(iota < tail, 16, -1), n_valid=tail
        )

    # warm-up compile UNTIMED: inserts are idempotent ORs of the same
    # seeded batches, so a full warm pass + clear leaves the timed pass
    # measuring steady-state device work (the fori_loop body compile is
    # tens of seconds and would otherwise dominate)
    f.words = loop_jit(f.words, full_steps)
    int(np.asarray(f.words.ravel()[0]))
    f.clear()
    t0 = time.perf_counter()
    f.words = loop_jit(f.words, full_steps)
    f.n_inserted += full_steps * B
    # to-value fence: block_until_ready can return early on this stack
    # (benchmarks/RESULTS_r3.md §1)
    int(np.asarray(f.words.ravel()[0]))
    t_insert = time.perf_counter() - t0
    n_timed = full_steps * B
    if tail:
        # the tail's single eager dispatch is a different program —
        # insert it (the queries and fill ratio see all
        # n keys) but OUTSIDE the timed window, which reports the
        # steady-state rate over the n_timed loop keys
        _tail_insert()
        int(np.asarray(f.words.ravel()[0]))
    # mixed-hit queries: half present (replay seed 0), half absent — one
    # jitted loop, XOR-accumulated so the fence waits for ALL
    if layout == "blocked":
        from tpubloom.filter import make_blocked_query_fn

        pure_query = make_blocked_query_fn(
            cfg, storage_fat=blocked_storage_fat(cfg)
        )
    else:
        from tpubloom.filter import make_query_fn as _mk_q

        pure_query = _mk_q(cfg)
    q_steps = max(1, nq // B)

    def _qloop(words):
        def body(i, acc):
            ku = jax.random.bits(
                jax.random.key(jnp.where(i % 2 == 0, 0, 10**6)),
                (B, 16), jnp.uint8,
            )
            return acc ^ pure_query(words, ku, lengths)

        return _lax.fori_loop(
            0, q_steps, body, jnp.zeros((B,), bool)
        )

    qloop_jit = jax.jit(_qloop)
    acc = qloop_jit(f.words)  # warm-up compile untimed
    int(np.asarray(jnp.sum(acc.astype(jnp.uint32))))
    t0 = time.perf_counter()
    acc = qloop_jit(f.words)
    int(np.asarray(jnp.sum(acc.astype(jnp.uint32))))  # to-value fence
    t_query = time.perf_counter() - t0
    qdone = q_steps * B
    return {
        "config": 2,
        "layout": layout,
        "m": cfg.m,
        "n_insert": n,
        "n_insert_timed": n_timed,
        "n_query": qdone,
        "insert_keys_per_sec": round(n_timed / t_insert),
        "query_keys_per_sec": round(qdone / t_query),
        "fill_ratio": round(f.fill_ratio(), 4),
    }


def config3(scale: float) -> dict:
    """Streaming insert with periodic checkpoints (tmp-dir file sink)."""
    import tempfile

    from tpubloom import BloomFilter, FilterConfig
    from tpubloom import checkpoint as ckpt
    from tpubloom.parallel.pipeline import StreamInserter

    n = int(1_000_000_000 * scale)
    log2m = 34 if scale >= 0.1 else 24
    cfg = FilterConfig(m=1 << log2m, k=7, key_len=28, key_name="stream-bench")
    f = BloomFilter(cfg)
    with tempfile.TemporaryDirectory() as td:
        sink = ckpt.FileSink(td)
        ins = StreamInserter(
            f, batch_size=1 << 16, sink=sink, checkpoint_every=max(n // 10, 1 << 16)
        )
        t0 = time.perf_counter()
        stats = ins.run((b"warc-record-%014d" % i for i in range(n)))
        elapsed = time.perf_counter() - t0
        ins.close()
        return {
            "config": 3,
            "m": cfg.m,
            "n": n,
            "stream_keys_per_sec": round(n / elapsed),
            "checkpoints_written": ins.checkpointer.checkpoints_written,
        }


def config4(scale: float, layout: str = "flat") -> dict:
    """Counting filter insert/delete/query mix. ``--layout blocked``
    selects the blocked counting variant (Pallas sweep hot loop on TPU,
    ~6x the flat scatter rate on v5e)."""
    import numpy as np

    from tpubloom import BlockedCountingBloomFilter, CountingBloomFilter, FilterConfig

    n = int(10_000_000 * scale)
    log2m = 30 if scale >= 0.1 else 22
    if layout == "blocked":
        cfg = FilterConfig(
            m=1 << log2m, k=7, key_len=16, counting=True, block_bits=512
        )
        f = BlockedCountingBloomFilter(cfg)
    else:
        cfg = FilterConfig(m=1 << log2m, k=7, key_len=16, counting=True)
        f = CountingBloomFilter(cfg)
    keys_u8, _ = _gen_keys(n)
    keys = [bytes(k) for k in keys_u8]
    half = keys[: n // 2]
    t0 = time.perf_counter()
    f.insert_batch(keys)
    f.delete_batch(half)
    hits = f.include_batch(keys)
    elapsed = time.perf_counter() - t0
    assert hits[n // 2 :].all()
    return {
        "config": 4,
        "layout": layout,
        "m": cfg.m,
        "ops": 2 * n + n // 2,
        "ops_per_sec": round((2 * n + n // 2) / elapsed),
    }


def config5(scale: float, layout: str = "flat") -> dict:
    """64-shard filter array over the available mesh."""
    import jax
    import numpy as np

    from tpubloom import FilterConfig
    from tpubloom.parallel.sharded import ShardedBloomFilter

    n = int(10_000_000 * scale)
    n_dev = len(jax.devices())
    log2m = 36 if scale >= 0.1 and n_dev >= 8 else 24
    cfg = FilterConfig(
        m=1 << log2m, k=7, key_len=16, shards=64,
        block_bits=512 if layout == "blocked" else 0,
    )
    f = ShardedBloomFilter(cfg)
    keys_u8, lengths = _gen_keys(min(n, 1 << 18))
    t0 = time.perf_counter()
    done = 0
    while done < n:
        f.insert_arrays(keys_u8, lengths)  # idempotent re-insert: rate only
        done += len(keys_u8)
    f.block_until_ready()
    t_insert = time.perf_counter() - t0
    t0 = time.perf_counter()
    hits = np.asarray(f.include_arrays(keys_u8, lengths))
    t_query = time.perf_counter() - t0
    assert hits.all()
    return {
        "config": 5,
        "layout": layout,
        "m": cfg.m,
        "shards": 64,
        "devices": n_dev,
        "insert_keys_per_sec": round(done / t_insert),
        "query_keys_per_sec": round(len(keys_u8) / t_query),
    }


CONFIGS = {1: config1, 2: config2, 3: config3, 4: config4, 5: config5}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, required=True, choices=sorted(CONFIGS))
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--platform", choices=["cpu", "tpu"], default=None)
    ap.add_argument(
        "--layout", choices=["flat", "blocked"], default="flat",
        help="filter layout for device configs 2, 4 and 5",
    )
    args = ap.parse_args()

    import jax

    if args.platform == "cpu" or (
        args.platform is None and "cpu" in os.environ.get("JAX_PLATFORMS", "")
    ):
        jax.config.update("jax_platforms", "cpu")
    on_tpu = jax.default_backend() not in ("cpu",)
    scale = args.scale if args.scale is not None else (1.0 if on_tpu else 0.001)

    if args.config in (2, 4, 5):
        result = CONFIGS[args.config](scale, layout=args.layout)
    else:
        result = CONFIGS[args.config](scale)
    result["scale"] = scale
    result["platform"] = jax.default_backend()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
