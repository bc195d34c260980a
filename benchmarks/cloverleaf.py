"""CloverLeaf weak scaling (paper Fig. 11).

The paper weak-scales the CloverLeaf hydrodynamics mini-app (structured-grid
stencil, memory-bandwidth-bound, MPI halo exchange) to 160 GH200s.  TPU
adaptation: the same 5-point stencil over a 2-D grid, sharded with
``shard_map``; halo exchange via ``jax.lax.ppermute`` along the mesh axis —
the JAX-native equivalent of the MPI halos.  On CPU this runs on 1 device
(the weak-scaling table derives per-size byte counts); on a pod the same code
scales across chips.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.hlo_analysis import HBM_BW


def _stencil(u):
    """5-point Jacobi update (CloverLeaf's diffusion-like kernel shape)."""
    c = u[1:-1, 1:-1]
    n = u[:-2, 1:-1]
    s = u[2:, 1:-1]
    w = u[1:-1, :-2]
    e = u[1:-1, 2:]
    return 0.2 * (c + n + s + w + e)


def make_step(mesh: Mesh):
    """shard_map step: halo exchange (ppermute) + local stencil."""

    def step(u):  # u: local (H_local, W) block, sharded over axis "x"
        up = jax.lax.ppermute(u[-1:], "x", [(i, (i + 1) % mesh.shape["x"]) for i in range(mesh.shape["x"])])
        down = jax.lax.ppermute(u[:1], "x", [(i, (i - 1) % mesh.shape["x"]) for i in range(mesh.shape["x"])])
        padded = jnp.concatenate([up, u, down], axis=0)
        padded = jnp.pad(padded, ((0, 0), (1, 1)), mode="edge")
        new = _stencil(padded)
        return new

    return jax.shard_map(step, mesh=mesh, in_specs=P("x", None), out_specs=P("x", None))


def run(sizes=(256, 512, 1024), iters: int = 5) -> list[dict]:
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(len(devs)), ("x",))
    rows = []
    for n in sizes:
        u = jnp.ones((n, n), jnp.float32)
        step = jax.jit(make_step(mesh))
        u2 = step(u)  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            u2 = step(u2)
        jax.block_until_ready(u2)
        dt = (time.perf_counter() - t0) / iters
        nbytes = 2 * n * n * 4  # read + write per cell
        rows.append(
            {
                "name": f"cloverleaf_{n}x{n}",
                "us_per_call": dt * 1e6,
                "bytes": nbytes,
                "modeled_v5e_us": nbytes / HBM_BW * 1e6,
                "halo_bytes_per_step": 2 * n * 4 * len(devs),
            }
        )
    # weak-scaling derivation: per-chip grid constant, halo/compute ratio
    for chips in (16, 64, 160, 256):
        n_local = 1024
        compute_bytes = 2 * n_local * n_local * 4
        halo_bytes = 2 * n_local * 4
        rows.append(
            {
                "name": f"cloverleaf_weakscale_{chips}chips",
                "us_per_call": compute_bytes / HBM_BW * 1e6,
                "derived": f"halo/compute bytes = {halo_bytes/compute_bytes:.2e} (weak-scaling efficiency ~ {1/(1+halo_bytes/compute_bytes):.4f})",
            }
        )
    return rows


def main() -> None:
    for r in run():
        d = r.get("derived", f"modeled_v5e_us={r.get('modeled_v5e_us', 0):.1f}")
        print(f"{r['name']},{r['us_per_call']:.1f},{d}")


if __name__ == "__main__":
    main()
