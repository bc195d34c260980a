"""Two runs of the tiny offline cell laid out over four devices (``chips`` 4,
``mesh.model`` 4), past the harness's look for a chip: a sound one, and one
in which each device keeps only its own partial sum of the MLP's
row-parallel down projection (the all-reduce left out). Prints one JSON line:
``{"sound": {...}, "no_allreduce": {...}, "placed": {...}}``, each run with
``correct``, ``checks``, ``metrics`` and ``device``, and ``placed`` with the
devices and partition spec of each weight the harness made.

Needs four devices before JAX starts; on the CPU:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 bench/tests/four_chips.py
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 31


def partial_sums_only(mesh):
    """The program's MLP with the down projection done per device on its
    slice of the hidden units, and no all-reduce of the partial sums."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.models.ffn import gate_fn

    def ffn(cfg, p, x, *, sh=None, fp8=None):
        h = gate_fn(cfg.activation)(x @ p["w_gate"].astype(x.dtype)) * (x @ p["w_up"].astype(x.dtype))
        local = jax.shard_map(
            lambda a, w: a @ w.astype(a.dtype),
            mesh=mesh,
            in_specs=(P(*([None] * (h.ndim - 1)), "model"), P("model", None)),
            out_specs=P(),
            check_vma=False,
        )
        return local(h, p["w_down"])

    return ffn


def main() -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness, weights
    from bench.tests.tiny import tiny_cell
    from repro.launch.mesh import make_serving_mesh
    from repro.models import blocks

    if jax.device_count() < 4:
        raise SystemExit(f"needs four devices, JAX found {jax.device_count()}")
    cell = tiny_cell("olmo-1b.offline", limit=0.05, chips=4)
    peaks = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}

    def run():
        out = harness.run(
            cell.name, seed=SEED, seconds=3.0, trace=False, t_start=time.perf_counter(), cell=cell,
            require_tpu=False, peaks_table=peaks,
        )
        return {k: out[k] for k in ("correct", "checks", "metrics", "device", "attempted", "failed")}

    placed, make = {}, weights.make

    def make_and_note(*a, **k):
        params = make(*a, **k)
        for path, leaf in jax.tree_util.tree_leaves_with_path(params):
            placed[jax.tree_util.keystr(path)] = [len(leaf.sharding.device_set), str(leaf.sharding.spec)]
        return params

    weights.make = make_and_note
    sound = run()
    weights.make = make
    blocks.ffn = partial_sums_only(make_serving_mesh(4))
    print(json.dumps({"sound": sound, "no_allreduce": run(), "placed": placed}), flush=True)


if __name__ == "__main__":
    main()
