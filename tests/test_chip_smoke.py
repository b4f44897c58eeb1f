"""Tier-1 CPU rehearsal of ``chip_smoke.py``.

The script's phases run here at a tiny m against a real subprocess
server on the CPU backend (8 fake devices, see conftest): the oracle
comparisons, the checkpoint restart and the sharded placement are the
same code the chip runs. The script itself, run as the driver runs it,
must refuse the CPU. The compile-cache helper it and the server call is
checked in child processes, so this process's JAX config stays as is.
"""

import json
import os
import subprocess
import sys

import jax

import chip_smoke
from tpubloom.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"m": 1 << 20, "k": 7, "key_len": 16, "block_bits": 512}
TINY_SHARDED = {**TINY, "m": 1 << 22, "shards": 16}


def test_single_chip_phases_match_oracle(tmp_path):
    report = chip_smoke.run_single(
        TINY, batch=1024, n_batches=4, seed=1, platform="cpu",
        count=jax.device_count(), want_path=None, workdir=str(tmp_path),
    )
    assert report["device"]["platform"] == "cpu"
    # off-TPU the auto choosers take the XLA paths
    assert report["paths"] == {
        "insert": "scatter", "test_insert": "scatter", "query": "gather",
    }
    t = report["timings"]
    assert t["boot1_first_answer_s"] > 0 and t["boot2_first_answer_s"] > 0


def test_sharded_phases_spread_rows_and_match_oracle(tmp_path):
    n = jax.device_count()
    report = chip_smoke.run_sharded(
        TINY_SHARDED, batch=1024, n_batches=2, seed=2, platform="cpu",
        count=n, workdir=str(tmp_path),
    )
    placement = report["placement"]
    assert len(placement) == n
    assert all(len(rows) == TINY_SHARDED["shards"] // n for rows in placement.values())


def test_script_refuses_the_cpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True,
        text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "device check" in proc.stderr


def _configure_in_child(env: dict) -> list:
    code = (
        "import jax, jax.numpy as jnp\n"
        "from tpubloom.utils import compile_cache\n"
        "print(compile_cache.configure())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=120, check=True,
    ).stdout
    return out.split()


def test_compile_cache_goes_where_the_env_says(tmp_path):
    env = {
        **os.environ,
        compile_cache.ENV: str(tmp_path),
        "JAX_ENABLE_COMPILATION_CACHE": "true",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    }
    assert _configure_in_child(env) == [str(tmp_path)] * 2
    assert os.listdir(tmp_path), "the compile was not cached in the env dir"


def test_compile_cache_defaults_to_the_checkout():
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV}
    # disabled caching (conftest): the path is set but nothing is written
    assert env["JAX_ENABLE_COMPILATION_CACHE"] == "false"
    path = os.path.join(REPO, ".jax_cache")
    assert compile_cache.CHECKOUT_CACHE_DIR == path
    assert _configure_in_child(env) == [path] * 2


def test_server_logs_the_device_it_holds(tmp_path):
    srv = chip_smoke.Server(str(tmp_path / "ckpt"), str(tmp_path / "s.log"))
    try:
        srv.wait_ready()
        dev = srv.device()
    finally:
        srv.stop()
    assert dev == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": jax.device_count(),
    }
    json.dumps(dev)
