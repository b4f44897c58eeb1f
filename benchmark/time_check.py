"""Time the post-window correctness check alone, on a synthetic history.

``python benchmark/time_check.py --mkeys 100 [--mix dedup-bulk]
[--config northstar-m32k7] [--reference DIR]`` builds the history a run
of the mix would leave with about ``--mkeys`` million inserted keys
(every caller's RPCs in turn, one after another on the clock, all
acknowledged, every verdict absent) and prints one JSON line: the
seconds ``reference.check`` took, the usable cores, and its counts.
``--reference`` takes ``reference.py`` and ``traffic.py`` from another
directory, such as an unpacked older commit's ``benchmark/``, to time
its check on the same history. No chip is used.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mkeys", type=float, required=True)
    ap.add_argument("--mix", default="dedup-bulk")
    ap.add_argument("--config", default="northstar-m32k7")
    ap.add_argument("--seed", type=int, default=2**31 + 5)
    ap.add_argument("--reference", default=HERE)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.reference))
    import numpy as np
    import reference
    import traffic

    mix = traffic.load(args.mix, os.path.join(HERE, "traffic"))
    with open(os.path.join(HERE, "configs", f"{args.config}.json")) as f:
        cfg = json.load(f)["filter"]
    callers, batch = int(mix["processes"]) * int(mix["threads"]), int(mix["batch"])
    per_caller = max(1, round(args.mkeys * 1e6 / batch / callers))
    absent = np.zeros(batch, bool)
    history = []
    for i in range(per_caller):
        for s in range(callers):
            t = 10 * len(history)
            history.append({"stream": s, "i": i, "op": traffic.op_of(mix, i), "send": t, "ack": t + 5,
                            "ok": True, "n": batch, "verdict": absent})
    inserted = sum(h["n"] for h in history if h["op"] in traffic.INSERTING)
    t0 = time.monotonic()
    got = reference.check(reference.Spec(cfg), traffic.Generator(mix, args.seed), history, args.seed,
                          int(mix["check_sample"]))
    seconds = time.monotonic() - t0
    print(json.dumps({"reference": os.path.abspath(reference.__file__), "mix": args.mix, "rpcs": len(history),
                      "inserted_keys": inserted, "cores": len(os.sched_getaffinity(0)),
                      "check_s": seconds, "counts": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
