"""AOT compiles of the main-path Pallas kernels for a described v5e chip.

The CPU suite runs every kernel in interpret mode, where Mosaic's limits
(tiling-aligned slices, scoped VMEM) do not apply. These tests hand the
real TPU compiler the kernels ``chip_smoke.py`` launches, at the
north-star width (m=2^32, block_bits=512, a 1M-key batch) with the
geometry the choosers pick, plus the blocked counting kernel at m=2^30.
Nothing runs: a pass says the chip's compiler accepts the kernel, not
that it is correct or fast.

The topology is described inside a fixture (never at import): only one
process may load the TPU library at a time, and xdist workers that
collected different tests would run none.
"""

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from tpubloom.config import FilterConfig
from tpubloom.ops import sweep

BATCH = 1 << 20
FLAGSHIP = FilterConfig(m=1 << 32, k=7, key_len=16, block_bits=512)
COUNTING = FilterConfig(m=1 << 30, k=7, key_len=16, block_bits=512, counting=True)


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep it out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _geometry(kind):
    nb, w = FLAGSHIP.n_blocks, FLAGSHIP.words_per_block
    if kind == "query":
        return FLAGSHIP, sweep.choose_fat_query_params(nb, BATCH, w)
    if kind == "counting":
        return COUNTING, sweep.choose_fat_params(
            COUNTING.n_blocks, BATCH, COUNTING.words_per_block, counting=True
        )
    return FLAGSHIP, sweep.choose_fat_params(
        nb, BATCH, w, presence=kind == "fused_insert"
    )


@pytest.mark.parametrize(
    "kind", ["fused_insert", "insert", "query", "counting"]
)
def test_kernel_compiles_for_v5e(one_chip, kind):
    config, geom = _geometry(kind)
    assert geom is not None, f"the chooser takes no sweep geometry for {kind}"
    fn, shapes = sweep.fat_kernel_shapes(
        config.n_blocks, config.words_per_block, geom,
        presence=kind == "fused_insert", counting=kind == "counting",
        query=kind == "query", batch=BATCH,
    )
    args = [
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
        for s in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
