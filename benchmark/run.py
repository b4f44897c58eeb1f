"""tpubloom's benchmark: one cell of ``BENCHMARK.json``, run end to end.

``python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` boots the gRPC server (``server_launch.py``, the only
process on the chip), creates the cell's filter empty, spawns the mix's
client processes (``client.py``), drives the mix until the server has
compiled every launch size it pads to (warm-up, counted in ``setup_s``),
then measures for ``--seconds``. Afterwards it stops the clients and the
server and holds a sample of every verdict the clients received to the
plain reference (``reference.py``). This process never starts JAX.

With ``--trace 0`` the last line carries the cell's end-to-end metrics;
with ``--trace 1`` a profiler trace of a few seconds inside the window
and ``/metrics`` deltas give the per-layer metrics, each read by
``layer_metrics/<name>.py``. Everything about a cell is found by name:
``configs/<config>.json`` and ``traffic/<mix>.json`` through
``BENCHMARK.json``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import queue
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

T_START = time.monotonic_ns()

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]
import reference  # noqa: E402
import traffic  # noqa: E402
from client import unary_rpc  # noqa: E402

FILTER = "bench"
TRACE_LEAD_S = 1.0
TRACE_S = 5.0
# the program's launch functions, by the name of their device program:
# (mutating, returns verdicts)
LAUNCH_KINDS = {"jit_query": (False, True), "jit_test_insert": (True, True), "jit_insert": (True, False)}
WARMUP_CAP_S = 900.0
REHEARSAL_PREFIX = "cpu_rehearsal."
WARM_STREAM = 1 << 20  # stream ids of the warm-up RPCs, above every mix's callers


class BenchError(Exception):
    """The run cannot produce a result: exit non-zero, print none."""


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """``server_launch.py`` as a child; commands over its stdin."""

    def __init__(self, flags: list, env: dict, log_path: str):
        self.port, self.metrics_port = _free_port(), _free_port()
        self.address = f"127.0.0.1:{self.port}"
        self.log_path = log_path
        self._replies: queue.Queue = queue.Queue()
        with open(log_path, "ab") as log:
            self.proc = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "server_launch.py"), str(self.port),
                 "--metrics-port", str(self.metrics_port), "--drain-grace", "1", *flags],
                cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=log, text=True,
            )
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            if line.startswith("BENCH "):
                self._replies.put(json.loads(line[6:]))

    def cmd(self, line: str, timeout: float = 120.0) -> dict:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()
        try:
            reply = self._replies.get(timeout=timeout)
        except queue.Empty:
            raise BenchError(f"server did not answer {line!r}")
        if "error" in reply:
            raise BenchError(f"server command {line!r}: {reply['error']}")
        return reply

    def wait_ready(self, timeout: float = 600.0) -> dict:
        from tpubloom.server.client import BloomClient

        deadline = time.monotonic() + timeout
        while True:
            if self.proc.poll() is not None:
                raise BenchError(f"server exited with {self.proc.returncode} during boot")
            try:
                with BloomClient(self.address, timeout=30.0, max_retries=0) as c:
                    return c.health()
            except Exception as e:  # noqa: BLE001 — still booting
                if time.monotonic() > deadline:
                    raise BenchError(f"server not ready in {timeout}s: {e}")
            time.sleep(0.25)

    def scrape(self) -> dict:
        """Every series of ``/metrics`` as ``{'name{labels}': value}``."""
        url = f"http://127.0.0.1:{self.metrics_port}/metrics"
        with urllib.request.urlopen(url, timeout=300) as r:
            text = r.read().decode()
        out = {}
        for line in text.splitlines():
            if line and not line.startswith("#"):
                key, _, val = line.rpartition(" ")
                try:
                    out[key] = float(val)
                except ValueError:
                    pass
        return out

    def stop(self) -> None:
        """SIGTERM: with no checkpoint directory the drain writes nothing."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()

    def tail(self, n: int = 4000) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()[-n:]


class Clients:
    """The mix's client processes."""

    def __init__(self, server: Server, mix_path: str, mix: dict, seed: int, workdir: str, env: dict):
        self.outs = [os.path.join(workdir, f"client{p}.npz") for p in range(int(mix["processes"]))]
        self.procs = [
            subprocess.Popen(
                [sys.executable, os.path.join(HERE, "client.py"), "--address", server.address,
                 "--filter", FILTER, "--mix", mix_path, "--seed", str(seed), "--proc", str(p),
                 "--out", out],
                cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            )
            for p, out in enumerate(self.outs)
        ]

    def _expect(self, word: str, timeout: float) -> None:
        for p in self.procs:
            box: list = []
            t = threading.Thread(target=lambda: box.append(p.stdout.readline()), daemon=True)
            t.start()
            t.join(timeout)
            if not box or box[0].strip() != word:
                raise BenchError(f"client did not say {word!r} (got {box[:1]}, rc={p.poll()})")

    def _send(self, word: str) -> None:
        for p in self.procs:
            p.stdin.write(word + "\n")
            p.stdin.flush()

    def start(self) -> None:
        self._expect("ready", 300)
        self._send("go")

    def finish(self) -> list:
        self._send("stop")
        self._expect("done", 400)
        for p in self.procs:
            p.wait(timeout=60)
        return self.outs

    def kill(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def load_history(outs: list) -> tuple[list, list]:
    """Every RPC record of every client, the clients' error texts, and
    how many callers never got their last answer."""
    history, errors, hung = [], [], 0
    for path in outs:
        z = np.load(path)
        verdicts, off = {}, 0
        bits = np.unpackbits(z["v_bits"]).astype(bool)
        for (s, i), n in zip(z["v_key"].tolist(), z["v_len"].tolist()):
            verdicts[(s, i)] = bits[off:off + n]
            off += n
        for stream, i, op, send, ack, ok, n in z["rows"].tolist():
            history.append({
                "stream": stream, "i": i, "op": traffic.OPS[op], "send": send, "ack": ack,
                "ok": bool(ok), "n": n, "verdict": verdicts.get((stream, i)),
            })
        errors.extend(z["errors"].tolist())
        hung += int(z["hung"])
    return history, errors, hung


def _fail(msg: str):
    raise BenchError(msg)


def _load_reader(name: str):
    path = os.path.join(HERE, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"layer_metrics_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a per-layer reader may read (``layer_metrics/*.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    @staticmethod
    def _delta(a: dict, b: dict, name: str, **labels) -> float:
        total = 0.0
        for key, val in b.items():
            base, _, lab = key.partition("{")
            if base != name:
                continue
            if any(f'{k}="{v}"' not in lab for k, v in labels.items()):
                continue
            total += val - a.get(key, 0.0)
        return total

    def delta(self, name: str, **labels) -> float:
        """Increase of a ``/metrics`` series over the measured window."""
        return self._delta(self.scrape0, self.scrape1, name, **labels)

    def trace_delta(self, name: str, **labels) -> float:
        """Increase of a ``/metrics`` series over the traced window."""
        return self._delta(self.trace_scrape0, self.trace_scrape1, name, **labels)

    def launches(self) -> list:
        """The filter launches in the trace: ``[(count, keys per launch,
        mutating, verdicts), ...]``. The count is the trace's own
        executions of each launch program; keys per launch are the
        coalescer's mean over the measured window for that kind."""
        d = self.delta
        q_flush = d("tpubloom_ingest_query_flushes_total")
        i_flush = d("tpubloom_ingest_fused_flushes_total") + d("tpubloom_ingest_plain_flushes_total")
        per = {
            "jit_query": d("tpubloom_keys_queried_total") / q_flush if q_flush else 0.0,
            "jit_test_insert": d("tpubloom_keys_inserted_total") / i_flush if i_flush else 0.0,
        }
        per["jit_insert"] = per["jit_test_insert"]
        counts: dict = {}
        for name, (n, _) in self.trace["modules"]["by_name"].items():
            kind = name.split("(")[0]
            if kind in LAUNCH_KINDS:
                counts[kind] = counts.get(kind, 0) + n / max(1, len(self.trace["devices"]))
        return [(n, per[k], *LAUNCH_KINDS[k]) for k, n in counts.items()]


def reduce_trace(trace_dir: str, env: dict) -> dict:
    """``trace_reduce.py`` in a child on the CPU (it reads the trace with
    JAX's own reader; this process stays off JAX)."""
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"), trace_dir],
        cwd=ROOT, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=240,
    )
    if out.returncode != 0:
        raise BenchError(f"trace reduction failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def warm_launch_sizes(client, mix: dict, seed: int) -> list:
    """One RPC of each op of the mix at each size the coalescer can merge
    its traffic to (``batch`` doubled up to ``warmup_max_keys``), one at a
    time, so that every program the window uses is compiled or loaded
    before it. The keys are fresh keys of streams of their own; the
    records join the history the reference checks. An RPC that fails
    (a cold compile can outlast the coalescer's 61 s budget) is recorded
    as failed, its effect unknown, and sent again."""
    gen = traffic.Generator(mix, seed)
    sizes, b = [], int(mix["batch"])
    while b <= int(mix["warmup_max_keys"]):
        sizes.append(b)
        b *= 2
    out = []
    for op in dict.fromkeys(mix["pattern"]):
        for size in sizes:
            stream = WARM_STREAM + len(out)
            keys = gen.keys_at(stream, 0, np.arange(size)).as_list()
            for _ in range(6):
                send = time.monotonic_ns()
                try:
                    v, ok = unary_rpc(client, FILTER, op, keys), True
                except Exception as e:  # noqa: BLE001 — retried; its effect stays unknown
                    say(f"warm-up {op} x{size} failed: {e}")
                    v, ok = None, False
                ack = time.monotonic_ns()
                out.append({"stream": stream, "i": 0, "op": op, "send": send, "ack": ack,
                            "ok": ok, "n": size, "verdict": v})
                if ok:
                    break
            say(f"warm-up {op} x{size}: {(ack - send) / 1e9:.3f} s")
    return out


def window_stats(history: list, t0: int, t1: int) -> dict:
    win = [h for h in history if t0 <= h["ack"] < t1]
    ok = [h for h in win if h["ok"]]
    lat = np.array([(h["ack"] - h["send"]) / 1e6 for h in ok], np.float64)
    # a failed RPC misses every latency limit
    lat_all = np.concatenate([lat, np.full(len(win) - len(ok), np.inf)])
    return {
        "rpcs": len(win), "failed": len(win) - len(ok), "keys": sum(h["n"] for h in ok),
        "ok_rpcs": len(ok), "client_s": float(lat.sum() / 1e3),
        "p50_ms": float(np.percentile(lat_all, 50)) if len(lat_all) else float("nan"),
        "p95_ms": float(np.percentile(lat_all, 95)) if len(lat_all) else float("nan"),
    }


def run(args) -> dict:
    spec_path = args.spec or os.path.join(ROOT, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    cell = next((w for w in spec["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        raise BenchError(f"no workload {args.workload!r} in {spec_path}")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    mix_dir = os.path.join(ROOT, spec.get("traffic_dir", "benchmark/traffic"))
    mix_path = os.path.join(mix_dir, f"{cell['traffic']}.json")
    mix = traffic.load(cell["traffic"], mix_dir)

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([ROOT] + [p for p in [env.get("PYTHONPATH")] if p])
    if args.rehearse_cpu:
        env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    else:
        env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    workdir = tempfile.mkdtemp(prefix="tpubloom-bench-")
    server = Server(cfg["server_flags"], env, os.path.join(workdir, "server.log"))
    clients = None
    try:
        server.wait_ready()
        t_ready = time.monotonic_ns()
        device = server.cmd("device")
        say(f"device: {device}")
        if not args.rehearse_cpu and (device["platform"] != "tpu" or device["count"] < cell["chips"]):
            raise BenchError(f"need {cell['chips']} TPU chip(s); JAX found {device}")
        from tpubloom.server.client import BloomClient

        with BloomClient(server.address, timeout=600.0) as c:
            c.create_filter(FILTER, config=cfg["filter"])
            warm = warm_launch_sizes(c, mix, args.seed)
        t_warmed = time.monotonic_ns()
        clients = Clients(server, mix_path, mix, args.seed, workdir, env)
        clients.start()
        t_warm = time.monotonic()

        def programs():
            c = server.cmd("compiles")
            return c["compiles"], c["cache_loads"]

        last = programs()
        last_change = time.monotonic()
        while True:
            time.sleep(0.5)
            now = programs()
            if now != last:
                last, last_change = now, time.monotonic()
            if time.monotonic() - t_warm >= mix["warmup_min_s"] and \
                    time.monotonic() - last_change >= mix["warmup_quiet_s"]:
                break
            if time.monotonic() - t_warm > WARMUP_CAP_S:
                say("warm-up cap reached with compiles still coming")
                break
        scrape0 = server.scrape() if args.trace else None
        c0 = server.cmd("compiles")
        t0 = time.monotonic_ns()
        setup_s = (t0 - T_START) / 1e9
        t1 = t0 + int(args.seconds * 1e9)
        trace_dir = ts0 = ts1 = None
        if args.trace:
            time.sleep(TRACE_LEAD_S)
            trace_dir = os.path.join(workdir, "trace")
            ts0 = server.scrape()
            tt0 = time.monotonic_ns()
            server.cmd("trace_start " + trace_dir)
            time.sleep(TRACE_S)
            # the counters' edge before the profiler's collection, which
            # takes seconds while the clients keep sending
            ts1 = server.scrape()
            tt1 = time.monotonic_ns()
            server.cmd("trace_stop", timeout=300)
        time.sleep(max(0.0, (t1 - time.monotonic_ns()) / 1e9))
        c1 = server.cmd("compiles")
        scrape1 = server.scrape() if args.trace else None
        t_closed = time.monotonic_ns()
        outs = clients.finish()
        memory = server.cmd("memory")
        server.stop()
        t_stopped = time.monotonic_ns()
        say(f"compiles in the window: " + json.dumps({k: c1[k] - c0[k] for k in c1}))
        history, errors, hung = load_history(outs)
        traffic_errors = sum(not h["ok"] for h in history) + hung
        history += warm
        for e in errors[:5]:
            say(f"client error: {e}")
        w = window_stats(history, t0, t1)
        say(f"window: {w['rpcs']} RPCs ({w['failed']} failed), {w['keys']} keys, "
            f"latency p50 {w['p50_ms']!r} ms p95 {w['p95_ms']!r} ms over {w['ok_rpcs']} samples")
        q = (t1 - t0) // 4
        say("keys per quarter of the window: " + " ".join(
            str(window_stats(history, t0 + j * q, t0 + (j + 1) * q)["keys"]) for j in range(4)))

        # -- correctness: every answer came, with as many verdicts as keys,
        # and a sample of verdicts agrees with the reference
        short = sum(1 for h in history if h["ok"] and h["verdict"] is not None and len(h["verdict"]) != h["n"])
        t_ref = time.monotonic_ns()
        got = reference.check(reference.Spec(cfg["filter"]), traffic.Generator(mix, args.seed),
                              history, args.seed, int(mix["check_sample"]))
        t_checked = time.monotonic_ns()
        say(f"reference: {got['sampled']} verdicts checked in {(t_checked - t_ref) / 1e9:.1f} s "
            f"on {len(os.sched_getaffinity(0))} cores")
        checks = {
            "rpc_errors": (traffic_errors, 0),
            "short_answers": (short, 0),
            "missed_acked_inserts": (got["missed"], 0),
            "phantom_present": (got["phantom"], 0),
        }
        correct = all(v <= lim for v, lim in checks.values()) and got["sampled"] > 0

        dev = {"platform": device["platform"], "kind": device["kind"], "count": device["count"],
               "memory_peak_bytes": memory["peak"]}
        # wall seconds of each stage of the run, so that a slow run shows where
        stages = {"setup": t0 - T_START, "boot": t_ready - T_START, "launch_warmup": t_warmed - t_ready,
                  "traffic_warmup": t0 - t_warmed, "window": t_closed - t0, "drain_stop": t_stopped - t_closed,
                  "history": t_ref - t_stopped, "check": t_checked - t_ref}
        prefix = REHEARSAL_PREFIX if args.rehearse_cpu else ""
        metrics: dict = {}
        breakdown = None
        if not args.trace:
            values = {"keys_per_s": w["keys"] / args.seconds, "batch_p95_ms": w["p95_ms"], "setup_s": setup_s}
            for m in spec["end_to_end"]:
                if args.workload in m.get("workloads", [args.workload]):
                    metrics[prefix + m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        else:
            tr = reduce_trace(trace_dir, env)
            stages["trace_reduce"] = time.monotonic_ns() - t_checked
            with open(os.path.join(HERE, "peaks.json")) as f:
                peaks = json.load(f)
            if device["kind"] not in peaks and not args.rehearse_cpu:
                raise BenchError(f"no peaks for device kind {device['kind']!r} in peaks.json")
            import work

            ctx = Context(
                scrape0=scrape0, scrape1=scrape1, trace_scrape0=ts0, trace_scrape1=ts1,
                window=w, trace_window=window_stats(history, tt0, tt1), trace=tr,
                cfg=cfg, mix=mix, peaks=peaks.get(device["kind"]), work=work, chips=device["count"],
                launch_kinds=LAUNCH_KINDS, on_chip=not args.rehearse_cpu, fail=_fail,
            )
            flushes = {k: ctx.trace_delta(f"tpubloom_ingest_{k}_flushes_total") for k in ("query", "fused", "plain")}
            say(f"launches in the trace: {ctx.launches()}; coalescer flushes over the traced span: {flushes}")
            for m in spec["per_layer"]:
                if args.workload not in m.get("workloads", [args.workload]):
                    continue
                val = _load_reader(m["name"])(ctx)
                if val is not None:
                    metrics[prefix + m["name"]] = {"value": val, "unit": m["unit"]}
            dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
            breakdown = {"device_ops": tr["device_ops"][:10], "idle_gaps": tr["idle_gaps"][:10]}
        stages["total"] = time.monotonic_ns() - T_START
        say("stage seconds: " + json.dumps({k: round(v / 1e9, 3) for k, v in stages.items()}))
        result = {"correct": bool(correct), "attempted": w["rpcs"], "failed": w["failed"],
                  "metrics": metrics, "device": dev}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
        print(f"sampled verdicts: {got['sampled']}", file=sys.stderr)
        for k, (v, lim) in checks.items():
            print(f"check {k}={v} limit={lim}", file=sys.stderr, flush=True)
        return result
    except BaseException:
        print(f"--- server log (tail)\n{server.tail()}", file=sys.stderr)
        raise
    finally:
        if args.keep:
            shutil.copytree(workdir, args.keep, dirs_exist_ok=True,
                            ignore=shutil.ignore_patterns("*.npz"))
        if clients is not None:
            clients.kill()
        if server.proc.poll() is None:
            server.proc.kill()
            server.proc.wait()
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spec", help=argparse.SUPPRESS)  # the tests' own BENCHMARK-like file
    ap.add_argument("--rehearse-cpu", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--keep", help=argparse.SUPPRESS)  # copy the trace and server log here
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except BenchError as e:
        print(f"bench: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
