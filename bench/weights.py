"""Random serving weights, made by the benchmark from the seed.

One jitted call builds the whole tree on the device(s), in bf16, straight
into the serving placement, so no weight passes through the host. The tree
has the layout of the configuration's reference module
(``bench/references/<config>.py``'s ``layout``), checked against the
program's abstract parameters before anything is built; the values are the
benchmark's own, so the plain reference can use them without taking anything
the program made.

A layout is ``{path: leaf}`` with ``/``-separated paths. A leaf is
``(shape, std)``, a normal draw scaled by ``std``; ``(shape, std, mean)``,
the same draw shifted by ``mean`` (a norm's scale); or ``{}``, a node that
holds no weight (a non-parametric norm). Weight ``i`` of the paths in sorted
order is drawn from the seed's key folded with ``i``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key that uses every bit of a seed wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def as_tree(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *parents, name = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree


def check_layout(layout: dict, abstract) -> None:
    """Refuse to run when the program's parameter tree is not this layout."""
    want = as_tree({p: tuple(leaf[0]) if leaf != {} else {} for p, leaf in layout.items()})
    got = jax.tree.map(lambda a: tuple(a.shape), abstract)
    is_shape = lambda x: isinstance(x, tuple)
    flat_want = {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(want, is_leaf=is_shape)}
    flat_got = {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(got, is_leaf=is_shape)}
    if flat_want != flat_got or jax.tree.structure(want, is_leaf=is_shape) != jax.tree.structure(got, is_leaf=is_shape):
        raise SystemExit(
            f"the program's parameter layout differs from the benchmark's: "
            f"benchmark {jax.tree.structure(want, is_leaf=is_shape)} {sorted(flat_want.items())} "
            f"program {jax.tree.structure(got, is_leaf=is_shape)} {sorted(flat_got.items())}"
        )


def make(layout: dict, seed: int, shardings) -> dict:
    """The weight tree from ``seed``, in bf16, placed by ``shardings`` (a
    tree of shardings matching the layout)."""

    def build(key):
        flat = {p: {} for p, leaf in layout.items() if leaf == {}}
        drawn = sorted((p, leaf) for p, leaf in layout.items() if leaf != {})
        for i, (path, (shape, std, *mean)) in enumerate(drawn):
            k = jax.random.fold_in(key, i)
            w = jax.random.normal(k, shape, jnp.bfloat16) * jnp.bfloat16(std)
            flat[path] = w + jnp.bfloat16(mean[0]) if mean else w
        return as_tree(flat)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
