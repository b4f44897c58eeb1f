#!/usr/bin/env python
"""DMA bandwidth vs lane width (extends hbm_probe's 16-vs-128 finding).

The fat sweep's update stream is [Btot, 128] u32 purely for DMA tile
alignment — only 18 lanes carry data. If 32- or 64-lane arrays DMA at
a usable fraction of the 128-lane rate, the stream can shrink 4x/2x
(both the host-side build write and the in-kernel window fetches).
This probe copies the same 256 MiB through a double-buffered manual-DMA
Pallas kernel at lane widths 16/32/64/128, to-value timing.

Run: timeout 900 python benchmarks/lane_probe.py
Writes benchmarks/out/lane_probe_r4.json.
"""

from __future__ import annotations

import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TOTAL_BYTES = 256 << 20
STEPS = 16
OUT_PATH = os.path.join(os.path.dirname(__file__), "out", "lane_probe_r4.json")
_rows = []


def emit(obj):
    print(json.dumps(obj), flush=True)
    _rows.append(obj)


def _copy_kernel(src_ref, out_ref, buf_ref, sems, *, rows_per_step: int, L: int):
    # manual double-buffered DMA: HBM src -> VMEM buf -> HBM out, like the
    # sweep kernel's window fetches (the auto-pipelined path would hide
    # the manual-DMA constraint we actually care about)
    p = pl.program_id(0)
    num_p = pl.num_programs(0)
    slot = lax.rem(p, 2)

    def fetch(s, pp):
        pltpu.make_async_copy(
            src_ref.at[pl.ds(pp * rows_per_step, rows_per_step), :],
            buf_ref.at[s],
            sems.at[s],
        ).start()

    @pl.when(p == 0)
    def _():
        fetch(0, 0)

    @pl.when(p + 1 < num_p)
    def _():
        fetch(1 - slot, p + 1)

    pltpu.make_async_copy(
        src_ref.at[pl.ds(0, rows_per_step), :], buf_ref.at[slot], sems.at[slot]
    ).wait()
    out_ref[...] = buf_ref[slot] + jnp.uint32(1)


def run_width(L: int):
    n_rows = TOTAL_BYTES // 4 // L
    rows_per_step = min(2048 * 128 // L, n_rows)
    while n_rows % rows_per_step:
        rows_per_step //= 2
    grid = n_rows // rows_per_step
    x = jnp.arange(n_rows * L, dtype=jnp.uint32).reshape(n_rows, L)

    fn = pl.pallas_call(
        functools.partial(_copy_kernel, rows_per_step=rows_per_step, L=L),
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)],
        out_specs=pl.BlockSpec((rows_per_step, L), lambda p: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, L), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((2, rows_per_step, L), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )

    def step(x):
        return fn(x)

    jit = jax.jit(step, donate_argnums=0)
    t0 = time.perf_counter()
    x = jit(x)
    int(np.asarray(x[0, 0]))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(STEPS):
        x = jit(x)
    int(np.asarray(x[0, 0]))
    dt = (time.perf_counter() - t0) / STEPS
    gbps = 2 * TOTAL_BYTES / dt / 1e9  # read + write
    emit({
        "lanes": L,
        "rows_per_step": rows_per_step,
        "ms": round(dt * 1e3, 2),
        "GBps_rw": round(gbps, 1),
        "compile_s": round(compile_s, 1),
    })


def main():
    emit({
        "probe": "manual-DMA copy bandwidth vs lane width",
        "bytes": TOTAL_BYTES,
        "platform": jax.default_backend(),
        "device": str(jax.devices()[0]),
    })
    for L in (128, 64, 32, 16, 128):  # repeat 128 to bracket drift
        try:
            run_width(L)
        except Exception as e:  # noqa: BLE001 — record the Mosaic refusal
            msg = str(e)
            key = "Slice shape along dimension 1 must be aligned"
            emit({
                "lanes": L,
                "error": (
                    "Mosaic rejects manual-DMA slices of sub-128-lane "
                    "arrays (it pads their HBM layout to 128 lanes, then "
                    "the slice is misaligned) — narrow update streams "
                    "are impossible; pack multiple updates per 128-lane "
                    "row instead"
                    if key in msg
                    else msg[:300]
                ),
            })
    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        for r in _rows:
            f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
