#!/usr/bin/env python
"""tpubloom benchmark — BASELINE north-star metric.

Measures batched insert+query throughput at m=2^32, k=7 (BASELINE.json
north_star: >= 1e9 keys/sec/chip on TPU v5e at <= 1% FPR) and prints ONE
JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Honest-measurement notes (SURVEY.md §6 feasibility):

* Keys are generated ON DEVICE inside the jitted step (jax.random.bits) —
  the host in this image has one CPU core and PCIe could never feed 1B
  16-byte keys/sec, so host->device ingestion is excluded by design and
  reported separately as `e2e_keys_per_sec` for a host-fed batch.
* One unit of work = one key inserted AND queried (the insert+query pair),
  matching the metric name "insert+query keys/sec".
* One process, on the chip or not at all: with no TPU (``platform ==
  "tpu"``) it exits non-zero and prints no result — a CPU number is
  never printed under the chip metric's name.
"""

from __future__ import annotations

import json
import os
import sys
import time

BASELINE_TARGET = 1e9  # keys/sec/chip, BASELINE.json north_star


def _run_bench() -> dict:
    """The measurement, on the TPU this process holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpubloom.config import FilterConfig
    from tpubloom.filter import (
        make_blocked_insert_fn,
        make_blocked_query_fn,
        make_blocked_test_insert_fn,
        make_insert_fn,
        make_query_fn,
    )

    # B = 8M: the optimum of the r5 batch sweep (not measured on
    # today's code)
    log2m, B, steps, key_len = 32, 1 << 23, 16, 16

    lengths = jnp.full((B,), key_len, jnp.int32)

    def measure(insert, query, state0, steps):
        """Fused insert+query step chain on device-generated keys.

        Returns (keys/sec, compile_s, kernel_s, final_state)."""

        def step(state, seed):
            keys = jax.random.bits(jax.random.key(seed), (B, key_len), jnp.uint8)
            state = insert(state, keys, lengths)
            hits = query(state, keys, lengths)
            return state, jnp.sum(hits.astype(jnp.uint32))

        step_jit = jax.jit(step, donate_argnums=0)
        t0 = time.perf_counter()
        state, hits = step_jit(state0, 0)
        # every timing fence forces a HOST VALUE off the carry, so the
        # clock stops only once the device work is done
        n_hits = int(np.asarray(hits))
        compile_s = time.perf_counter() - t0
        assert n_hits == B, "keys inserted in-step must all be found"
        state, _ = step_jit(state, 1)
        t0 = time.perf_counter()
        acc = None
        for i in range(2, 2 + steps):
            state, acc = step_jit(state, i)
        _ = int(np.asarray(acc))
        kernel_s = time.perf_counter() - t0
        return B * steps / kernel_s, compile_s, kernel_s, state

    # -- flagship: blocked (cache-line) layout, FUSED test-and-insert —
    # one device pass per batch performs the insert AND answers pre-batch
    # membership per key (the insert+query pair of the metric; the
    # reference's Lua add script has the same fused semantics).
    blk_config = FilterConfig(m=1 << log2m, k=7, key_len=key_len, block_bits=512)
    # fat [NB/J, 128] storage — the layout persistent filters actually
    # hold; the logical [NB, W] entry pays a real reshape copy per pass
    # (~26 ms at m=2^32, benchmarks/RESULTS_r3.md §2)
    from tpubloom.filter import blocked_device_shape, blocked_storage_fat

    blk_fat = blocked_storage_fat(blk_config)
    blk_insert = make_blocked_insert_fn(blk_config, storage_fat=blk_fat)
    blk_query = make_blocked_query_fn(blk_config, storage_fat=blk_fat)
    blk_ti = make_blocked_test_insert_fn(blk_config, storage_fat=blk_fat)
    blk_state0 = jnp.zeros(blocked_device_shape(blk_config), jnp.uint32)

    def fused_step(state, seed):
        keys = jax.random.bits(jax.random.key(seed), (B, key_len), jnp.uint8)
        state, present = blk_ti(state, keys, lengths)
        return state, jnp.sum(present.astype(jnp.uint32))

    fused_jit = jax.jit(fused_step, donate_argnums=0)
    t0 = time.perf_counter()
    blk_state, n_pre = fused_jit(blk_state0, 0)
    _ = int(np.asarray(n_pre))  # host-value fence (see measure)
    blk_compile = time.perf_counter() - t0
    # sanity: replaying the same keys must report every key present
    blk_state, n_rep = fused_jit(blk_state, 0)
    assert int(np.asarray(n_rep)) == B, "replayed batch must be fully present"
    t0 = time.perf_counter()
    acc = None
    for i in range(1, 1 + steps):
        blk_state, acc = fused_jit(blk_state, i)
    _ = int(np.asarray(acc))
    blk_kernel = time.perf_counter() - t0
    blk_rate = B * steps / blk_kernel

    # split (separate insert step + query step) rate, for comparison
    split_steps = max(8, steps // 2)
    split_rate, _, _, blk_state = measure(
        blk_insert, blk_query, blk_state, split_steps
    )

    # each half on its own (VERDICT r5: the fused headline plus both
    # single-op rates so the presence/query costs are visible)
    def ins_step(state, seed):
        keys = jax.random.bits(jax.random.key(seed), (B, key_len), jnp.uint8)
        state = blk_insert(state, keys, lengths)
        return state, jnp.sum(
            state[:: max(1, state.shape[0] // 64)], dtype=jnp.uint32
        )

    ins_jit = jax.jit(ins_step, donate_argnums=0)
    blk_state, acc = ins_jit(blk_state, 999)
    _ = int(np.asarray(acc))
    half_steps = max(8, steps // 2)
    t0 = time.perf_counter()
    for i in range(1000, 1000 + half_steps):
        blk_state, acc = ins_jit(blk_state, i)
    _ = int(np.asarray(acc))
    insert_only_rate = B * half_steps / (time.perf_counter() - t0)

    def qry_step(state, carry, seed):
        keys = jax.random.bits(
            jax.random.key(seed ^ (carry & 0xFF)), (B, key_len), jnp.uint8
        )
        hits = blk_query(state, keys, lengths)
        return jnp.sum(hits.astype(jnp.uint32))

    qry_jit = jax.jit(qry_step)
    carry = qry_jit(blk_state, jnp.uint32(0), 0)
    _ = int(np.asarray(carry))
    # --profile-dir (ISSUE 12): dump a jax.profiler trace of the
    # query-only loop with per-step TraceAnnotations — the occupancy
    # evidence ROADMAP item 2 asks for (open in Perfetto/XProf; the
    # per-PHASE stage breakdown lives in benchmarks/profile_query.py).
    # Profiling adds tracer overhead, so the profiled loop's rate is
    # flagged rather than silently recorded as a clean number.
    profile_dir = os.environ.get("TPUBLOOM_BENCH_PROFILE_DIR")
    t0 = time.perf_counter()
    if profile_dir:
        from tpubloom.utils import tracing

        with tracing.trace(os.path.join(profile_dir, "query_only")):
            for i in range(1, 1 + half_steps):
                with tracing.annotate("query_only_step", i=i, batch=B):
                    carry = qry_jit(blk_state, carry, i)
            _ = int(np.asarray(carry))
    else:
        for i in range(1, 1 + half_steps):
            carry = qry_jit(blk_state, carry, i)
        _ = int(np.asarray(carry))
    kernel_query_s = time.perf_counter() - t0
    query_only_rate = B * half_steps / kernel_query_s

    # -- reference-compatible flat layout (the Redis-bitmap position spec)
    config = FilterConfig(m=1 << log2m, k=7, key_len=key_len)
    insert = make_insert_fn(config)
    query = make_query_fn(config)
    flat_steps = max(6, steps // 3)  # flat is the slow path; sample it
    flat_rate, _, _, _ = measure(
        insert, query, jnp.zeros((config.n_words,), jnp.uint32), flat_steps
    )

    # end-to-end rate with host-packed keys (the gRPC-server ingest path),
    # on the flagship blocked path. Fixed 1M host batch regardless of the
    # device batch B: this measures host ingestion on the 1-core host, and
    # a larger sample only burns untimed setup. The per-phase split uses
    # the same phase names as the server's /metrics breakdown
    # (host_prep / h2d / kernel / d2h — tpubloom.obs.context).
    from tpubloom.utils.packing import pack_keys

    Bh = min(B, 1 << 20)
    rng = np.random.default_rng(0)
    raw_keys = [rng.bytes(key_len) for _ in range(Bh)]
    insert_jit = jax.jit(blk_insert, donate_argnums=0)
    query_jit = jax.jit(blk_query)
    phases = {}
    t0 = time.perf_counter()
    ku8, kl = pack_keys(raw_keys, key_len)
    phases["host_prep_s"] = time.perf_counter() - t0
    blk_state = insert_jit(blk_state, ku8, kl)  # compile for this path
    t0 = time.perf_counter()
    ku8_d, kl_d = jnp.asarray(ku8), jnp.asarray(kl)
    jax.block_until_ready((ku8_d, kl_d))
    phases["h2d_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    blk_state = insert_jit(blk_state, ku8_d, kl_d)
    hits = query_jit(blk_state, ku8_d, kl_d)
    jax.block_until_ready(hits)
    phases["kernel_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    hits_np = np.asarray(hits)  # D2H of the verdicts is part of e2e
    phases["d2h_s"] = time.perf_counter() - t0
    # e2e keeps its historical definition (h2d + kernel + d2h — what the
    # rounds-1..5 records measured) so the number stays comparable;
    # host_prep is reported in the phase breakdown only
    e2e_s = phases["h2d_s"] + phases["kernel_s"] + phases["d2h_s"]
    assert bool(hits_np.all())

    # FPR sanity at the end state of the flagship chain. Distinct-key
    # accounting: fused chain used seeds 0..steps; the split re-measure
    # runs seeds 0..split_steps+1 (covered by the fused chain); the
    # insert-only loop added 1 + half_steps batches at fresh seeds
    # (999, 1000..); the query-only loop inserts nothing.
    n_inserted = (
        B * (1 + steps)
        + B * max(0, split_steps + 1 - steps)
        + Bh
        + B * (1 + half_steps)
    )
    probe = jax.random.bits(jax.random.key(10_000_019), (B, key_len), jnp.uint8)
    fpr = float(np.asarray(query_jit(blk_state, probe, lengths)).mean())

    from tpubloom.ops.sweep import effective_query_path, resolve_insert_path

    insert_path = resolve_insert_path(blk_config, B)
    query_path = effective_query_path(blk_config, B)
    return {
        "metric": f"batched insert+query keys/sec/chip @ m=2^{log2m}, k=7",
        "value": round(blk_rate),
        "unit": "keys/sec",
        "vs_baseline": round(blk_rate / BASELINE_TARGET, 6),
        "device": {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        },
        "layout": "blocked512",
        "op": "fused test-and-insert (pre-batch membership + insert per key)",
        "insert_path": insert_path,
        "query_path": query_path,
        "split_keys_per_sec": round(split_rate),
        "insert_only_keys_per_sec": round(insert_only_rate),
        # the read-path trajectory (ISSUE 12): BENCH rounds track the
        # query-only rate and its loop time from r06 on, so the query
        # kernel's effect is a first-class number next to kernel_s
        "query_only_keys_per_sec": round(query_only_rate),
        "kernel_query_s": round(kernel_query_s, 4),
        "query_profiled": bool(profile_dir),
        "m": blk_config.m,
        "k": blk_config.k,
        "batch": B,
        "steps": steps,
        "compile_s": round(blk_compile, 2),
        "kernel_s": round(blk_kernel, 4),
        "flat_keys_per_sec": round(flat_rate),
        "e2e_keys_per_sec": round(Bh / e2e_s),
        "e2e_phases": {k: round(v, 5) for k, v in phases.items()},
        "e2e_phases_note": (
            "same phase vocabulary as the server's "
            "tpubloom_rpc_phase_seconds /metrics histogram "
            "(host_prep/h2d/kernel/d2h; bench has no decode/encode)"
        ),
        "e2e_note": (
            "host-fed rate: H2D of a host-packed batch + kernel + D2H; "
            "compare split_keys_per_sec for the device-side rate"
        ),
        "observed_fpr": fpr,
        "n_inserted": n_inserted,
    }


def main() -> None:
    # --profile-dir <path>: capture a jax.profiler trace of the measured
    # loops (per-step TraceAnnotations; benchmarks/profile_query.py has
    # the per-STAGE harness)
    if "--profile-dir" in sys.argv:
        i = sys.argv.index("--profile-dir")
        if i + 1 >= len(sys.argv):
            print("--profile-dir needs a path", file=sys.stderr)
            raise SystemExit(2)
        os.environ["TPUBLOOM_BENCH_PROFILE_DIR"] = os.path.abspath(
            sys.argv[i + 1]
        )
    from tpubloom.utils import compile_cache

    compile_cache.configure()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench.py: no TPU (JAX found {platform!r})", file=sys.stderr)
        raise SystemExit(1)
    print(json.dumps(_run_bench()))


if __name__ == "__main__":
    main()
