"""ISSUE 11 satellites: the geometry-probe machinery in
:mod:`tpubloom.ops.sweep` — persistent on-disk cache keyed by device
kind (a second process start performs ZERO speculative probe compiles),
shape-identical probe buffers (ADVICE r5 #1), one compile per probe
(a failure is real, not retried), failed probes never persisted, the
packed-KBJ bound on the validated-set fast path (ADVICE r5 #3), and
backend errors that propagate instead of reading as "not a TPU".

All off-TPU: ``_probe_env`` / ``_probe_compile`` are the deliberate
seams — the tests monkeypatch them so the cache/signature logic runs
under the CPU backend exactly as it would on an unvalidated TPU
generation.
"""

import jax
import jax.numpy as jnp
import pytest

from tpubloom.ops import sweep

NB, BATCH, W = 1 << 17, 4096, 16


def _new_process(monkeypatch=None):
    """Simulate a fresh process: in-memory probe caches emptied, the
    on-disk cache (TPUBLOOM_CACHE_DIR) left alone."""
    sweep._GEOM_PROBE_CACHE.clear()
    sweep._GEOM_DISK_CACHE.clear()
    sweep._GEOM_DISK_LOADED.clear()


@pytest.fixture()
def fake_tpu(monkeypatch, tmp_path):
    """Pretend to be an unvalidated TPU generation with a recording
    probe; restore every module-global cache afterwards."""
    monkeypatch.setenv("TPUBLOOM_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sweep, "_probe_env", lambda: "Fake TPU v9")
    calls = []

    def probe(fn, blocks_sds, upd_sds, starts_sds):
        calls.append(upd_sds.shape)
        return True, None

    monkeypatch.setattr(sweep, "_probe_compile", probe)
    saved = (
        dict(sweep._GEOM_PROBE_CACHE),
        dict(sweep._GEOM_DISK_CACHE),
        set(sweep._GEOM_DISK_LOADED),
    )
    _new_process()
    yield calls
    sweep._GEOM_PROBE_CACHE.clear()
    sweep._GEOM_PROBE_CACHE.update(saved[0])
    sweep._GEOM_DISK_CACHE.clear()
    sweep._GEOM_DISK_CACHE.update(saved[1])
    sweep._GEOM_DISK_LOADED.clear()
    sweep._GEOM_DISK_LOADED.update(saved[2])


def test_second_start_pays_zero_probe_compiles(fake_tpu):
    """THE acceptance gate: the first start probes (and persists), a
    simulated second process start on the same device kind answers
    every probe from disk — zero speculative compiles."""
    geom = sweep.choose_fat_params(NB, BATCH, W)
    assert geom is not None
    first = len(fake_tpu)
    assert first >= 1, "an unvalidated kind must probe at least once"
    _new_process()  # fresh process, same TPUBLOOM_CACHE_DIR
    geom2 = sweep.choose_fat_params(NB, BATCH, W)
    assert geom2 == geom
    assert len(fake_tpu) == first, (
        f"second start re-compiled {len(fake_tpu) - first} probe(s) — "
        f"the on-disk cache must absorb the cold start"
    )


def test_probe_upd_buffer_is_shape_identical_to_runtime(fake_tpu):
    """ADVICE r5 #1: the probe's update buffer must carry the REAL
    runtime row count (the _fat_stream btot for this batch), not a
    kbjp+16 stand-in."""
    geom = sweep.choose_fat_params(NB, BATCH, W)
    assert geom is not None
    J, R8, S, KJ, KBJ = geom
    pk = sweep.fat_pack(W, False)
    if pk == 1:
        expect = BATCH + KBJ + sweep._ALIGN
    else:
        expect = -(-BATCH // pk) + sweep._packed_rows(KBJ, pk) + sweep._ALIGN
    assert (expect, 128) in fake_tpu, (
        f"no probe used the runtime row count {expect}; saw {fake_tpu}"
    )


def test_failed_probe_demotes_but_is_not_persisted(monkeypatch, tmp_path):
    """A failed probe demotes THIS process (cached False in memory) but
    never lands on disk — a restart re-probes, preserving the
    transient-compile-failure escape hatch the warning documents."""
    monkeypatch.setenv("TPUBLOOM_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sweep, "_probe_env", lambda: "Fake TPU v9")
    calls = []
    monkeypatch.setattr(
        sweep, "_probe_compile",
        lambda *a: (calls.append(1), (False, RuntimeError("OOM")))[1],
    )
    _new_process()
    with pytest.warns(RuntimeWarning, match="failed its probe"):
        geom = sweep.choose_fat_params(NB, BATCH, W)
    assert geom is None, "every candidate geometry must demote"
    n1 = len(calls)
    # same process: cached False, no re-probe
    assert sweep.choose_fat_params(NB, BATCH, W) is None
    assert len(calls) == n1
    # "restart": the failure must NOT have persisted — re-probes run
    _new_process()
    with pytest.warns(RuntimeWarning):
        sweep.choose_fat_params(NB, BATCH, W)
    assert len(calls) > n1, "a restart must re-probe failed geometries"
    _new_process()


def test_disk_put_merges_with_concurrent_writers(fake_tpu, tmp_path):
    """Fleet rolling restarts share one cache dir: a write must UNION
    with entries a sibling process landed after our load — not clobber
    the file with this process's view alone."""
    sweep._geom_disk_put("Fake TPU v9", "mine/1")
    # a "sibling process" writes its own entry directly
    from tpubloom.utils import crcjson

    path = sweep._geom_cache_path("Fake TPU v9")
    crcjson.store(path, {
        "geoms": ["sibling/2"], "salt": sweep._geom_cache_salt(),
    })
    sweep._geom_disk_put("Fake TPU v9", "mine/3")
    _new_process()
    assert sweep._geom_disk_get("Fake TPU v9", "sibling/2"), (
        "a sibling's entry was clobbered by our whole-file rewrite"
    )
    assert sweep._geom_disk_get("Fake TPU v9", "mine/1")
    assert sweep._geom_disk_get("Fake TPU v9", "mine/3")


def test_version_salt_invalidates_persisted_probes(fake_tpu, monkeypatch):
    """A persisted ok=True must not survive a code/jax upgrade: a
    geometry that no longer compiles would skip its probe and hit the
    Mosaic error at first REAL use, with no demotion path."""
    geom = sweep.choose_fat_params(NB, BATCH, W)
    assert geom is not None
    first = len(fake_tpu)
    monkeypatch.setattr(
        sweep, "_geom_cache_salt", lambda: "upgraded|jax-99.0"
    )
    _new_process()
    assert sweep.choose_fat_params(NB, BATCH, W) == geom
    assert len(fake_tpu) > first, (
        "a salt change must force re-probing, not trust stale entries"
    )


def test_probe_compile_failure_is_not_retried():
    """Compiles are local, so one failed probe compile is a real limit:
    it is reported at once (no retry), with its cause."""
    state = {"n": 0}

    def failing(a, b, c):
        state["n"] += 1
        raise RuntimeError("scoped VMEM exhausted")

    sds = jax.ShapeDtypeStruct((8, 128), jnp.uint32)
    ok, exc = sweep._probe_compile(failing, sds, sds, sds)
    assert not ok and state["n"] == 1
    assert "scoped VMEM" in str(exc)


def test_probe_env_propagates_backend_errors(monkeypatch):
    """A backend that fails to initialise must not read as "not a TPU"
    (that would skip every probe and hide the device)."""
    def broken():
        raise RuntimeError("TPU backend failed to initialise")

    monkeypatch.setattr(sweep.jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        sweep._probe_env()


def test_validated_signature_bounds_packed_kbj(monkeypatch, tmp_path):
    """ADVICE r5 #3: the v5e validated-set fast path now also pins the
    big-fetch scratch — a geometry whose packed KBJ rows exceed what
    its (J, R8, S, KJP) signature can legitimately pair with must
    PROBE, not ride the fast path."""
    # caps derive from inverting the chooser's KJ(lambda) step function
    cap = sweep._validated_kbjp_cap("presence", (8, 512, 2, 96))
    assert cap > 0
    monkeypatch.setenv("TPUBLOOM_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sweep, "_probe_env", lambda: "TPU v5 lite")
    probed = []
    monkeypatch.setattr(
        sweep, "_probe_compile",
        lambda *a: (probed.append(1), (True, None))[1],
    )
    _new_process()
    pk = sweep.fat_pack(16, True)
    # reconstruct an unpacked KJ whose packed rows hit the validated 96
    kj = next(
        k for k in range(16, 2048, 8) if sweep._packed_rows(k, pk) == 96
    )
    ok_kbj = next(
        b for b in range(kj, 1 << 16, 8)
        if sweep._packed_rows(b, pk) == cap
    )
    geom_ok = (8, 512, 2, kj, ok_kbj)
    assert sweep._fat_geometry_compiles(
        1 << 17, 16, geom_ok, presence=True, counting=False, batch=BATCH
    )
    assert not probed, "an in-signature geometry must skip the probe"
    big_kbj = next(
        b for b in range(ok_kbj, 1 << 20, 8)
        if sweep._packed_rows(b, pk) > cap
    )
    geom_big = (8, 512, 2, kj, big_kbj)
    assert sweep._fat_geometry_compiles(
        1 << 17, 16, geom_big, presence=True, counting=False, batch=BATCH
    )
    assert probed, "an out-of-cap KBJ must fall through to the probe"
    _new_process()
