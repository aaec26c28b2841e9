"""Random weights from the seed, made on the device in one jitted call, in
the type they are served in, and the same weights again one layer at a
time for the reference.

Every leaf has a key of its own (the seed's key folded with the leaf's
index), and every layer of a stacked leaf the leaf's key folded with the
layer's index. So the reference can regenerate layer ``l`` alone, and gets
the same values that the program was given.

The layout (paths, shapes, dtypes) is the served program's parameter tree;
the harness checks it against the program's own before a run.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    path: Tuple
    shape: Tuple[int, ...]  # per layer where ``stacked``
    dtype: str
    kind: str  # dense | embed | scale | bias
    fan_in: int
    stacked: bool


def layout(cfg: Dict) -> List[Leaf]:
    d, hd, ff, v = cfg["d_model"], cfg["head_dim"], cfg["d_ff"], cfg["vocab"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv"] * hd
    w = cfg["param_dtype"]
    ln = cfg["norm"] == "layernorm"
    out = [Leaf(("embed", "table"), (v, d), w, "embed", d, False)]
    if not cfg["tie_embeddings"]:
        out.append(Leaf(("lm_head",), (v, d), w, "dense", d, False))
    out.append(Leaf(("final_norm", "scale"), (d,), "float32", "scale", d, False))
    if ln:
        out.append(Leaf(("final_norm", "bias"), (d,), "float32", "bias", d, False))
    blk = ("blocks", 0)
    for norm in ("norm_mixer", "norm_ffn"):
        out.append(Leaf(blk + (norm, "scale"), (d,), "float32", "scale", d, True))
        if ln:
            out.append(Leaf(blk + (norm, "bias"), (d,), "float32", "bias", d, True))
    out += [
        Leaf(blk + ("attn", "wq", "w"), (d, q), w, "dense", d, True),
        Leaf(blk + ("attn", "wk", "w"), (d, kv), w, "dense", d, True),
        Leaf(blk + ("attn", "wv", "w"), (d, kv), w, "dense", d, True),
        Leaf(blk + ("attn", "wo", "w"), (q, d), w, "dense", q, True),
        Leaf(blk + ("mlp", "w_up"), (d, ff), w, "dense", d, True),
        Leaf(blk + ("mlp", "w_gate"), (d, ff), w, "dense", d, True),
        Leaf(blk + ("mlp", "w_down"), (ff, d), w, "dense", ff, True),
    ]
    if cfg["qkv_bias"]:
        # last, so that the keys of every other leaf stay as they are
        out += [Leaf(blk + ("attn", p, "b"), (n,), w, "bias", d, True)
                for p, n in (("wq", q), ("wk", kv), ("wv", kv))]
    return out


def root_key(seed: int) -> jax.Array:
    """All 64 low bits of the seed: ``jax.random.key`` keeps only 32."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF
    )


def _draw(key, leaf: Leaf, layer) -> jax.Array:
    k = jax.random.fold_in(key, layer)
    x = jax.random.normal(k, leaf.shape, jnp.float32)
    if leaf.kind == "dense":
        x = x * (leaf.fan_in ** -0.5)
    elif leaf.kind == "scale":
        x = 1.0 + 0.1 * x
    elif leaf.kind == "bias":
        x = 0.1 * x
    return x.astype(leaf.dtype)


def _leaf_key(root, i: int):
    return jax.random.fold_in(root, i)


def _set(tree: Dict, path: Tuple, value) -> None:
    node = tree
    for p in path[:-1]:
        if p == 0:
            continue
        node = node.setdefault(p, {})
    node[path[-1]] = value


def _finish(tree: Dict) -> Dict:
    if "blocks" in tree:
        tree["blocks"] = (tree["blocks"],)
    return tree


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init(leaves: Tuple[Leaf, ...], n_layers: int, root):
    tree: Dict = {}
    for i, leaf in enumerate(leaves):
        key = _leaf_key(root, i)
        if leaf.stacked:
            x = jax.vmap(lambda l: _draw(key, leaf, l))(jnp.arange(n_layers))
        else:
            x = _draw(key, leaf, 0)
        _set(tree, leaf.path, x)
    return _finish(tree)


def init_params(cfg: Dict, seed: int):
    """The whole served parameter tree, on the default device."""
    return _init(tuple(layout(cfg)), cfg["n_layers"], root_key(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(leaves: Tuple[Tuple[int, Leaf], ...], root, layer):
    return {leaf.path[2:]: _draw(_leaf_key(root, i), leaf, layer) for i, leaf in leaves}


def layer_weights(cfg: Dict, seed: int, layer: int) -> Dict[Tuple, jax.Array]:
    """Layer ``layer``'s leaves, keyed by their path below the block."""
    leaves = tuple((i, l) for i, l in enumerate(layout(cfg)) if l.stacked)
    return _layer(leaves, root_key(seed), jnp.int32(layer))


@functools.partial(jax.jit, static_argnums=(0,))
def _top(leaves: Tuple[Tuple[int, Leaf], ...], root):
    return {leaf.path: _draw(_leaf_key(root, i), leaf, 0) for i, leaf in leaves}


def top_weights(cfg: Dict, seed: int) -> Dict[Tuple, jax.Array]:
    """The leaves outside the layers: embedding, LM head, final norm."""
    leaves = tuple((i, l) for i, l in enumerate(layout(cfg)) if not l.stacked)
    return _top(leaves, root_key(seed))
