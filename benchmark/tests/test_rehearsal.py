"""The whole command, rehearsed on the CPU at tiny sizes.

``--rehearse-cpu`` lets the run proceed on the CPU and prefixes every
metric name, so that no CPU run prints a cell's metric. The same runs
with the timed path broken underneath (``TPUBLOOM_BENCH_FAULT``) must
come out not correct, and so must the control.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT

SPEC = os.path.join(ROOT, "benchmark", "tests", "data", "spec.json")
PREFIX = "cpu_rehearsal."
FOUR = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def run(workload, *, seed=2**31 + 9, seconds=3, trace=0, fault=None, rehearse=True,
        cwd=ROOT, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "TPUBLOOM_BENCH_FAULT"}
    env.setdefault("JAX_PLATFORMS", "cpu")
    if fault:
        env["TPUBLOOM_BENCH_FAULT"] = fault
    env.update(env_extra or {})
    cmd = [sys.executable, "benchmark/run.py", "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace), "--spec", SPEC]
    if rehearse:
        cmd.append("--rehearse-cpu")
    p = subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=600)
    last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
    result = json.loads(last) if last.startswith("{") else None
    return p, result


@pytest.mark.parametrize("workload", ["tiny-dedup", "tiny-mixed", "tiny-url"])
def test_rehearsal_is_correct_and_names_no_cell_metric(workload):
    p, r = run(workload)
    assert p.returncode == 0, p.stderr[-3000:]
    assert r["correct"] is True and r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] and all(k.startswith(PREFIX) for k in r["metrics"])
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"
    assert "check missed_acked_inserts=0 limit=0" in p.stderr


def test_traced_rehearsal_reads_per_layer_metrics():
    p, r = run("tiny-mixed", seconds=6, trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    assert r["correct"] is True
    names = {k[len(PREFIX):] for k in r["metrics"]}
    assert {"wire_ms_per_rpc", "coalesce_keys_per_launch", "device_idle_pct"} <= names
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert len(r["breakdown"]["device_ops"]) <= 10


def test_stage_seconds_come_before_the_result_line():
    """The run names the wall seconds of each of its stages on stdout,
    and the result stays the last line."""
    p, r = run("tiny-dedup", seed=2**33 + 28)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    stage = [ln for ln in lines if ln.startswith("bench: stage seconds: ")]
    assert len(stage) == 1 and lines.index(stage[0]) < len(lines) - 1 and r["correct"] is True
    stages = json.loads(stage[0][len("bench: stage seconds: "):])
    assert list(stages) == ["setup", "boot", "launch_warmup", "traffic_warmup", "window", "drain_stop",
                            "history", "check", "total"]
    assert all(v >= 0 for v in stages.values()) and stages["window"] >= 3
    assert stages["total"] >= stages["setup"] + stages["window"] + stages["check"]


def test_sharded_rehearsal_on_four_virtual_devices():
    p, r = run("tiny-sharded-dedup", env_extra=FOUR)
    assert p.returncode == 0, p.stderr[-3000:]
    assert r["correct"] is True and r["device"]["count"] == 4


@pytest.mark.parametrize("workload,fault,env", [
    ("tiny-dedup", "unchanged", None),
    ("tiny-dedup", "half", None),
    ("tiny-dedup", "altered", None),
    ("tiny-sharded-dedup", "no_exchange", FOUR),
])
def test_broken_timed_path_is_not_correct(workload, fault, env):
    p, r = run(workload, fault=fault, env_extra=env)
    assert p.returncode == 0, p.stderr[-3000:]
    assert r["correct"] is False


# tiny-url is left out: its repeats lag 6 frames behind their inserts,
# so a write held back by one flush is rarely queried (PERF.md section 7)
@pytest.mark.parametrize("workload", ["tiny-dedup", "tiny-mixed"])
def test_control_write_behind_is_not_correct(workload):
    p, r = run(workload, fault="write_behind")
    assert p.returncode == 0, p.stderr[-3000:]
    assert r["correct"] is False
    assert r["checks"]["missed_acked_inserts"]["value"] > 0


def test_no_accelerator_no_result():
    p, r = run("tiny-dedup", rehearse=False)
    assert p.returncode != 0 and r is None
    assert "need 1 TPU chip(s)" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PATH": os.environ["PATH"], "HOME": str(tmp_path), "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "ns-dedup-bulk",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{") and '"correct"' not in p.stdout
