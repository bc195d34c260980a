"""Random serving weights, made by the benchmark from the seed.

One jitted call builds the whole tree on the device(s), in bf16, straight
into the serving placement, so no weight passes through the host. The tree
has the program's parameter layout (checked against the program's abstract
parameters before anything is built); the values are the benchmark's own, so
the plain reference can use them without taking anything the program made.

Layout of the dense family with OLMo's non-parametric LayerNorm and a tied
output head (L layers): ``embed.tok (V, D)``, ``blocks.attn.{wq (L, D, H, hd),
wk (L, D, KV, hd), wv (L, D, KV, hd), wo (L, H, hd, D)}``,
``blocks.mlp.{w_gate (L, D, F), w_up (L, D, F), w_down (L, F, D)}``, and empty
``blocks.norm1/norm2`` and ``final_norm``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key that uses every bit of a seed wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def layout(cfg: dict) -> dict:
    """``{path: (shape, std)}``."""
    L, D, F, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    arch = cfg["architecture"]
    if arch["norm"] != "layernorm_nonparametric" or not arch["tie_word_embeddings"]:
        raise ValueError(f"no weight layout for norm {arch['norm']!r} with tied head {arch['tie_word_embeddings']}")
    return {
        "embed/tok": ((V, D), 1 / math.sqrt(D)),
        "blocks/attn/wq": ((L, D, H, hd), 1 / math.sqrt(D)),
        "blocks/attn/wk": ((L, D, KV, hd), 1 / math.sqrt(D)),
        "blocks/attn/wv": ((L, D, KV, hd), 1 / math.sqrt(D)),
        "blocks/attn/wo": ((L, H, hd, D), 1 / math.sqrt(H * hd)),
        "blocks/mlp/w_gate": ((L, D, F), 1 / math.sqrt(D)),
        "blocks/mlp/w_up": ((L, D, F), 1 / math.sqrt(D)),
        "blocks/mlp/w_down": ((L, F, D), 1 / math.sqrt(F)),
    }


def as_tree(flat: dict, cfg: dict) -> dict:
    tree: dict = {"blocks": {"norm1": {}, "norm2": {}}, "final_norm": {}}
    for path, leaf in flat.items():
        *parents, name = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree


def check_layout(cfg: dict, abstract) -> None:
    """Refuse to run when the program's parameter tree is not this layout."""
    want = as_tree({p: s for p, (s, _) in layout(cfg).items()}, cfg)
    got = jax.tree.map(lambda a: tuple(a.shape), abstract)
    flat_want = {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(want, is_leaf=lambda x: isinstance(x, tuple))}
    flat_got = {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(got, is_leaf=lambda x: isinstance(x, tuple))}
    if flat_want != flat_got:
        raise SystemExit(
            f"the program's parameter layout differs from the benchmark's: "
            f"benchmark {sorted(flat_want.items())} program {sorted(flat_got.items())}"
        )


def make(cfg: dict, seed: int, shardings) -> dict:
    """The weight tree from ``seed``, in bf16, placed by ``shardings`` (a
    tree of shardings matching the layout)."""
    spec = layout(cfg)

    def build(key):
        flat = {}
        for i, (path, (shape, std)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            flat[path] = jax.random.normal(k, shape, jnp.bfloat16) * jnp.bfloat16(std)
        return as_tree(flat, cfg)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
