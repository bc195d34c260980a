"""Production mesh construction (assignment-mandated shape).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — only the dry-run
launcher, which sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import, ever builds the full mesh.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType

from repro.config import MeshConfig


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules place
    arrays with ``NamedSharding``s and let GSPMD propagate (the installed
    JAX defaults new meshes to ``Explicit`` sharding-in-types)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips) mesh.

    Axis semantics: "pod" is the DCN boundary (data-parallel across pods),
    "data" the intra-pod FSDP/DP axis, "model" the TP/EP/SP axis.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(cfg: MeshConfig):
    return make_production_mesh(multi_pod=cfg.multi_pod)


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (CPU) devices exist — for tests."""
    return _auto_mesh((data, model), ("data", "model"))


def make_serving_mesh(tp: int = 1):
    """(data=1, model=tp) mesh for tensor-parallel serving.

    One model instance spans ``tp`` devices — the paper's 4-way Grace-Hopper
    node is ``tp=4``.  The serving engine shards params and paged KV pools
    over the "model" axis; the data axis is kept (size 1) so the standard
    sharding rule tables apply unchanged.  On CPU, force multiple host
    devices first: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    if tp < 1:
        raise ValueError(f"tp={tp} (need >= 1)")
    avail = jax.device_count()
    if tp > avail:
        raise ValueError(
            f"tp={tp} exceeds {avail} visible device(s); on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp} before "
            f"importing jax"
        )
    return make_local_mesh(1, tp)


def make_replica_meshes(replicas: int, tp: int = 1) -> list:
    """Partition the visible devices into ``replicas`` disjoint
    ``(data=1, model=tp)`` meshes — one independent serving engine per
    slice, the multi-replica analogue of ``make_serving_mesh``.

    Replica ``i`` owns devices ``[i*tp, (i+1)*tp)``, so replicas never
    contend for a device and one replica's failure cannot corrupt a peer's
    state — the isolation the router's failover model assumes.  Requires
    ``replicas * tp <= jax.device_count()``; on CPU force host devices
    first (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    if replicas < 1:
        raise ValueError(f"replicas={replicas} (need >= 1)")
    if tp < 1:
        raise ValueError(f"tp={tp} (need >= 1)")
    need = replicas * tp
    avail = jax.device_count()
    if need > avail:
        raise ValueError(
            f"replicas={replicas} x tp={tp} needs {need} devices, have "
            f"{avail}; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} before "
            f"importing jax"
        )
    devs = jax.devices()
    return [
        jax.sharding.Mesh(
            np.asarray(devs[i * tp : (i + 1) * tp]).reshape(1, tp), ("data", "model")
        )
        for i in range(replicas)
    ]
