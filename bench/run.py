#!/usr/bin/env python3
"""Run one benchmark cell on the chip(s) of this machine.

    python3 bench/run.py --workload olmo-1b.offline --seed 7 --seconds 20 --trace 0

``--workload`` names an entry of ``BENCHMARK.json``'s ``workloads``. With
``--trace 0`` the last line of standard output is the result with the cell's
end-to-end metrics; with ``--trace 1`` a profiler trace of the middle half of
the window gives its per-layer metrics and the ``breakdown``. Without a TPU,
or with fewer chips than the cell needs, it exits non-zero and prints no
result. JAX's persistent compilation cache lives in ``.jax_cache`` at the
root of the checkout, so only the first run of a cell compiles.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.path[0] = str(ROOT)  # the checkout root, not bench/ itself
    from bench.entry import prepare

    prepare()
    from bench import harness

    result = harness.run(args.workload, seed=args.seed, seconds=args.seconds, trace=bool(args.trace), t_start=T_START)
    harness.print_result(result)


if __name__ == "__main__":
    main()
