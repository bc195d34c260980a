#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate it sustains.

    python3 bench/sweep.py --workload olmo-1b.shared-prefix --rates 1,2,3,4 --seconds 20 --seed 5

Runs the cell once per rate, in this one process (which alone holds the
chip), with the mix's ``rate_per_s`` replaced, and prints one JSON line per
rate: requests due and finished, TTFT and queue-wait percentiles, and the
queue wait of the first and the last quarter of arrivals. Past the knee the
queue grows all through the window, so the last quarter waits far longer
than the first. The cell's mix file then gets 0.8 of the knee as its fixed
rate; the benchmark's own runs never search.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    sys.path[0] = str(ROOT)  # the checkout root, not bench/ itself
    from bench.entry import prepare

    prepare()
    from bench import harness, spec
    from bench.record import percentile

    base = spec.load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(base.mix)
        mix["arrival"]["rate_per_s"] = rate
        cell = dataclasses.replace(base, mix=mix)
        out = harness.run(
            args.workload, seed=args.seed, seconds=args.seconds, trace=False,
            t_start=time.perf_counter(), cell=cell, keep_record=True,
        )
        reqs = out["_record"].requests
        q = len(reqs) // 4
        wait = lambda rs: percentile([r.admit_wait for r in rs if r.admit_wait is not None], 50)
        ttft = [(r.token_t[0] - r.due_t) for r in reqs if r.token_t]
        print(json.dumps({
            "rate_per_s": rate,
            "due": len(reqs),
            "finished": sum(1 for r in reqs if r.finish == "length"),
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p95_s": percentile(ttft, 95),
            "queue_wait_p50_first_quarter_s": wait(reqs[:q]),
            "queue_wait_p50_last_quarter_s": wait(reqs[-q:]),
            "metrics": out["metrics"],
        }), flush=True)


if __name__ == "__main__":
    main()
