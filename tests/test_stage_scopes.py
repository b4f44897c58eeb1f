"""Named scopes on the filter programs' device stages: every stage a
program has — hash, sort, sweep, unsort — names its ops in the lowered
HLO's location metadata (``tpubloom/<stage>``), which a device profile
carries as each op's ``op_name``."""

import re

import jax
import jax.numpy as jnp
import pytest

from tpubloom import filter as F
from tpubloom.config import FilterConfig

NB, BB, K, B = 8192, 512, 7, 8192  # a shape the fat sweeps take (CPU interpret)
SWEEP_CFG = FilterConfig(
    m=NB * BB, k=K, key_len=16, block_bits=BB,
    insert_path="sweep", query_path="sweep",
)


def _stages(fn, blocks_shape) -> set:
    args = (
        jax.ShapeDtypeStruct(blocks_shape, jnp.uint32),
        jax.ShapeDtypeStruct((B, 16), jnp.uint8),
        jax.ShapeDtypeStruct((B,), jnp.int32),
    )
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    return set(re.findall(r"tpubloom/(hash|sort|sweep|unsort)\b", text))


@pytest.mark.parametrize(
    "program,stages",
    [
        ("test_insert", {"hash", "sort", "sweep", "unsort"}),
        ("insert", {"hash", "sort", "sweep"}),
        ("query", {"hash", "sort", "sweep", "unsort"}),
        ("flat_insert", {"hash"}),
    ],
)
def test_program_carries_every_stage_scope(program, stages):
    fat = (NB * SWEEP_CFG.words_per_block // 128, 128)
    make = {
        "test_insert": lambda: F.make_blocked_test_insert_fn(
            SWEEP_CFG, storage_fat=True),
        "insert": lambda: F.make_blocked_insert_fn(
            SWEEP_CFG, storage_fat=True),
        "query": lambda: F.make_blocked_query_fn(
            SWEEP_CFG, storage_fat=True),
        "flat_insert": lambda: F.make_insert_fn(
            FilterConfig(m=1 << 20, k=K, key_len=16)),
    }[program]
    shape = ((1 << 20) // 32,) if program == "flat_insert" else fat
    assert _stages(make(), shape) == stages
