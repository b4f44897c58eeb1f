"""The readers of the coalescer dispatcher's histograms, on a hand-made
pair of ``/metrics`` scrapes; and on a pair from a program that files
none of them, where each reads nothing."""

import importlib.util
import os

import pytest

import run
from conftest import BENCH

WALL = "tpubloom_ingest_flush_phase_seconds_sum"
CPU = "tpubloom_ingest_flush_phase_cpu_seconds_sum"


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _series(family, op, phase):
    return f'{family}{{op="{op}",phase="{phase}"}}'


# the window's increase of each series: scrape1 = scrape0 + these
GROWTH = {
    "tpubloom_uptime_seconds": 50.0,
    "tpubloom_ingest_keys_coalesced_total": 4_000_000.0,
    # host steps, insert + query flushes: 2.0 s wall, 1.5 s CPU
    _series(WALL, "insert", "merge"): 0.25, _series(CPU, "insert", "merge"): 0.25,
    _series(WALL, "insert", "unpack"): 0.75, _series(CPU, "insert", "unpack"): 0.5,
    _series(WALL, "insert", "host_prep"): 0.5, _series(CPU, "insert", "host_prep"): 0.25,
    _series(WALL, "query", "log"): 0.0, _series(CPU, "query", "log"): 0.0,
    _series(WALL, "query", "demux"): 0.5, _series(CPU, "query", "demux"): 0.5,
    # device side: 6.0 s wall
    _series(WALL, "insert", "lock_wait"): 0.5, _series(WALL, "insert", "h2d"): 0.5,
    _series(WALL, "insert", "kernel"): 0.25, _series(WALL, "query", "kernel_query"): 0.25,
    _series(WALL, "insert", "d2h"): 4.0, _series(WALL, "insert", "settle"): 0.5,
    # the dispatcher idled 10 s of the 50
    "tpubloom_ingest_dispatch_idle_seconds_sum": 10.0,
    "tpubloom_ingest_dispatch_idle_seconds_count": 400.0,
    'tpubloom_ingest_park_wait_seconds_sum{op="insert"}': 3.0,
    'tpubloom_ingest_park_wait_seconds_count{op="insert"}': 1000.0,
    'tpubloom_ingest_park_wait_seconds_sum{op="query"}': 1.0,
    'tpubloom_ingest_park_wait_seconds_count{op="query"}': 1000.0,
}


def _ctx(growth):
    scrape0 = {k: 100.0 for k in growth}
    scrape1 = {k: 100.0 + v for k, v in growth.items()}
    return run.Context(scrape0=scrape0, scrape1=scrape1)


def test_flush_readers_on_a_hand_made_pair_of_scrapes():
    ctx = _ctx(GROWTH)
    assert _reader("flush_host_ms_per_mkey")(ctx) == pytest.approx(2.0 / 4.0 * 1e3)
    assert _reader("flush_host_offcpu_pct")(ctx) == pytest.approx(25.0)
    assert _reader("flush_device_wait_ms_per_mkey")(ctx) == pytest.approx(6.0 / 4.0 * 1e3)
    assert _reader("park_wait_ms")(ctx) == pytest.approx(2.0)
    assert _reader("dispatcher_busy_pct")(ctx) == pytest.approx(80.0)


@pytest.mark.parametrize("name", [
    "flush_host_ms_per_mkey", "flush_host_offcpu_pct", "flush_device_wait_ms_per_mkey",
    "park_wait_ms", "dispatcher_busy_pct",
])
def test_a_program_without_the_histograms_reads_nothing(name):
    ctx = _ctx({"tpubloom_uptime_seconds": 50.0,
                "tpubloom_ingest_keys_coalesced_total": 4_000_000.0})
    assert _reader(name)(ctx) is None
