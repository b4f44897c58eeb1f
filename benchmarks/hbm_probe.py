#!/usr/bin/env python
"""HBM bandwidth / grid-step overhead probe (trustworthy-timing edition).

A plain Pallas copy kernel over the north-star block array (512 MiB) at
several tile sizes R separates the two costs in time(R) = P*c_step +
bytes/BW: small R exposes per-step overhead, large R approaches the DMA
bandwidth ceiling. Timing uses long chained loops forced to a host VALUE.
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NB, W = 1 << 23, 16  # 512 MiB of u32
STEPS = 32


def _copy_kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:] + jnp.uint32(1)


def run(R, alias: bool):
    P = NB // R
    fn = pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((NB, W), jnp.uint32),
        grid=(P,),
        in_specs=[pl.BlockSpec((R, W), lambda p: (p, 0))],
        out_specs=pl.BlockSpec((R, W), lambda p: (p, 0)),
        input_output_aliases={0: 0} if alias else {},
    )

    def step(x):
        y = fn(x)
        return y

    jit = jax.jit(step, donate_argnums=(0,) if alias else ())
    x = jnp.zeros((NB, W), jnp.uint32)
    x = jit(x)
    _ = int(np.asarray(x[0, 0]))
    t0 = time.perf_counter()
    for _i in range(STEPS):
        x = jit(x)
    v = int(np.asarray(x[0, 0]))
    dt = (time.perf_counter() - t0) / STEPS
    print(
        json.dumps(
            {
                "R": R, "P": P, "alias": alias,
                "ms": round(dt * 1e3, 3),
                "us_per_step": round(dt / P * 1e6, 3),
                "GBps_rw": round(2 * NB * W * 4 / dt / 1e9, 1),
                "check": v,
            }
        ),
        flush=True,
    )


def run_fat(R8, alias=True):
    """Same 512 MiB viewed as [NB/8, 128]: full-lane tiles. The (8, 128)
    DMA tiling makes 16-lane tiles waste 8x of the transfer."""
    NB8 = NB // 8
    P = NB8 // R8
    fn = pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((NB8, 128), jnp.uint32),
        grid=(P,),
        in_specs=[pl.BlockSpec((R8, 128), lambda p: (p, 0))],
        out_specs=pl.BlockSpec((R8, 128), lambda p: (p, 0)),
        input_output_aliases={0: 0} if alias else {},
    )
    jit = jax.jit(lambda x: fn(x), donate_argnums=(0,) if alias else ())
    x = jnp.zeros((NB8, 128), jnp.uint32)
    x = jit(x)
    _ = int(np.asarray(x[0, 0]))
    t0 = time.perf_counter()
    for _i in range(STEPS):
        x = jit(x)
    v = int(np.asarray(x[0, 0]))
    dt = (time.perf_counter() - t0) / STEPS
    print(
        json.dumps(
            {
                "fat_R8": R8, "P": P, "alias": alias,
                "ms": round(dt * 1e3, 3),
                "GBps_rw": round(2 * NB * W * 4 / dt / 1e9, 1),
                "check": v,
            }
        ),
        flush=True,
    )


def main():
    for R8 in (64, 512, 4096):
        run_fat(R8)
    for R in (512, 2048, 8192):
        run(R, alias=True)
    run(8192, alias=False)


if __name__ == "__main__":
    main()
