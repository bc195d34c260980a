#!/usr/bin/env python3
"""Readings that the correctness limit of a cell is set from.

    python3 bench/calibrate.py --workload olmo-1b.offline --seeds 1001-1012 --seconds 20

Runs the cell on each seed in this one process (which alone holds the chip)
and, after each window, reads two numbers over the same sampled requests:
the widest reference-logit gap of a token the program served (the number
``correct`` compares), and the same gap for the token that the control, the
configuration's reference (``bench/references/<config>.py``) computed in its
lower precision (``CONTROL``: float8 e4m3 for a bf16 configuration), puts
first at each position. The limit in
``bench/configs/<config>.json`` lies between the largest program reading
(the lower) and the smallest control reading (the upper). Each seed's line
gives both verdicts: ``program_correct``, and ``control_correct``, the
harness's own ``correct`` with the control in the program's place, which has
to be false. One JSON line per seed, then a summary line.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def seeds_of(text: str) -> list:
    if "-" in text:
        lo, hi = (int(x) for x in text.split("-"))
        return list(range(lo, hi + 1))
    return [int(x) for x in text.split(",")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="a-b or comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path[0] = str(ROOT)  # the checkout root, not bench/ itself
    from bench.entry import prepare

    prepare()
    from bench import harness

    lower, upper = [], []
    for seed in seeds_of(args.seeds):
        out = harness.run(args.workload, seed=seed, seconds=args.seconds, trace=False, t_start=time.perf_counter(), control=True)
        cal = out["_calibration"]
        lower.append(max(cal["gaps"]) if cal["gaps"] else None)
        upper.append(max(cal["control_gaps"]) if cal["control_gaps"] else None)
        print(json.dumps({"seed": seed, "program": lower[-1], "control": upper[-1], "control_correct": out["correct"],
                          "control_checks": out["checks"], "metrics": out["metrics"],
                          "memory_peak_bytes": out["device"]["memory_peak_bytes"], **cal}), flush=True)
    ok_l = [x for x in lower if x is not None]
    ok_u = [x for x in upper if x is not None]
    print(json.dumps({"summary": True, "lower": max(ok_l) if ok_l else None, "upper": min(ok_u) if ok_u else None,
                      "program": lower, "control": upper}), flush=True)


if __name__ == "__main__":
    main()
