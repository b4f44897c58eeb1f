"""Test harness config: run JAX on CPU with 8 fake devices.

SURVEY.md §4.2 item 3 — multi-chip without a cluster:
``xla_force_host_platform_device_count=8`` fakes 8 devices so
shard_map/collective tests run anywhere, replacing the reference's
"just need a local redis-server" property. ``JAX_PLATFORMS=cpu`` is set
before jax is imported, so no test process reaches for a TPU.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# servers the tests spawn call compile_cache.configure(): keep their CPU
# compiles out of the checkout's persistent cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="module")
def lock_check_armed(tmp_path_factory):
    """ISSUE 6: arm the runtime lock-order / held-while-blocking tracker
    (:mod:`tpubloom.utils.locks`) for a whole chaos module.

    In-process services are covered by ``set_enabled(True)`` — every lock
    constructed while the module runs is a tracked, named lock feeding
    the acquisition graph. Caveat: module-level singleton locks
    (``faults._lock``, ``obs.counters._lock``, ``native``'s build lock)
    are constructed at import/collection time, so in a local run without
    ``TPUBLOOM_LOCK_CHECK=1`` in the environment they stay bare and
    untracked; the CI chaos shard exports the env var, which is where
    those singletons get full coverage. Subprocess servers (the
    SIGKILL-failover and
    drain scenarios spawn real children) inherit ``TPUBLOOM_LOCK_CHECK``
    plus a report directory through ``os.environ``; each child that
    exits cleanly dumps a ``lockcheck-<pid>.json`` report there
    (SIGKILLed children can't — that's fine, their locks were tracked
    until the kill and the survivors' reports still land).

    Teardown asserts ZERO violations across the in-process tracker and
    every subprocess report — a new lock-order cycle or a blocking call
    under a registry/filter lock anywhere in the chaos run fails the
    module, which is the ISSUE-6 acceptance gate."""
    from pathlib import Path

    from tpubloom.utils import locks

    # ISSUE 13: when the environment already names a report dir (the CI
    # chaos shard sets one so the reports survive as artifacts and the
    # analysis job replays them through `python -m tpubloom.analysis`),
    # keep collecting there instead of a throwaway tmp dir. All armed
    # modules then share one dir — each teardown re-diffs earlier
    # modules' (clean) reports, which is harmless and makes the gate
    # fleet-wide rather than per-module.
    preset = os.environ.get(locks.REPORT_DIR_ENV)
    if preset:
        report_dir = Path(preset)
        report_dir.mkdir(parents=True, exist_ok=True)
        # stale reports from an EARLIER pytest run (a developer's
        # exported env var, a reused runner) would be re-diffed against
        # today's manifest and fail a clean tree — clear them ONCE per
        # process, so the armed modules of THIS run still accumulate
        # into the shared dir for the CI artifact
        if not getattr(lock_check_armed, "_preset_cleared", False):
            lock_check_armed._preset_cleared = True
            for stale in report_dir.glob("lockcheck-*.json"):
                stale.unlink()
    else:
        report_dir = tmp_path_factory.mktemp("lockcheck")
    saved = {
        k: os.environ.get(k) for k in (locks.ENV_VAR, locks.REPORT_DIR_ENV)
    }
    os.environ[locks.ENV_VAR] = "1"
    os.environ[locks.REPORT_DIR_ENV] = str(report_dir)
    locks.set_enabled(True)
    locks.reset()
    yield
    vios = list(locks.violations())
    locks.set_enabled(None)
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    for path in sorted(report_dir.glob("lockcheck-*.json")):
        rep = json.loads(path.read_text())
        vios.extend(
            {**v, "subprocess": path.name} for v in rep["violations"]
        )
    assert not vios, (
        "lock-check violations recorded during the module:\n"
        + "\n".join(
            f"  [{v.get('subprocess', 'in-process')}] {v['kind']}: "
            f"{v['message']} @ {v['site']}"
            for v in vios
        )
    )


@pytest.fixture(scope="module")
def lock_order_manifest(lock_check_armed):
    """ISSUE 13: the lock-ORDER closure gate, shared by every armed
    chaos module (faults/ha/sync_repl joined cluster/ingest this PR).
    After the whole armed module ran, every acquisition edge in the
    runtime graph — the in-process tracker AND the subprocess exit
    reports — must be DECLARED in the lock-order manifest
    (``tpubloom/analysis/lock_order.py``). An undeclared edge anywhere
    in the armed fleet is a test failure: new lock nesting is a
    reviewed design decision, not an accident discovered at 3am.

    Depends on ``lock_check_armed`` so this teardown runs FIRST (while
    the tracker is still armed and the report dir env var still
    points at this module's collected subprocess reports)."""
    import glob

    from tpubloom.analysis import lock_order
    from tpubloom.utils import locks

    yield
    findings = lock_order.check_live()
    report_dir = os.environ.get(locks.REPORT_DIR_ENV, "")
    if report_dir and os.path.isdir(report_dir):
        for path in sorted(
            glob.glob(os.path.join(report_dir, "lockcheck-*.json"))
        ):
            with open(path) as f:
                findings.extend(
                    {**v, "report": os.path.basename(path)}
                    for v in lock_order.check_report(json.load(f))
                )
    assert not findings, (
        "undeclared lock-order edges (declare deliberately in "
        "tpubloom/analysis/lock_order.py or fix the nesting):\n"
        + "\n".join(f"  {f['message']}" for f in findings)
    )
