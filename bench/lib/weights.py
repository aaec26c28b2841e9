"""Random weights from the seed, made on the device in one jitted call, in
the type they are served in, and the same weights again one layer at a
time for the reference.

Every leaf has a key of its own (the seed's key folded with the leaf's
index in the layout), and every layer of a stacked leaf the leaf's key
folded with the layer's index. So the reference can regenerate layer ``l``
alone, and gets the same values that the program was given.

The layout (paths, shapes, dtypes) is the served program's parameter tree,
as the configuration's family module (``bench/reference/<family>.py``)
lists it; the harness checks it against the program's own before a run.
An integer in a path is a position in a tuple (``("blocks", 0, ...)`` is
the first group of stacked layers).
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
    stacked: int  # layers in the leaf's stack; 0 for a leaf outside the layers


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
        node = node.setdefault(p, {})
    node[path[-1]] = value


def _tuples(node):
    """Dicts keyed 0..n-1 become tuples, bottom up."""
    if not isinstance(node, dict):
        return node
    out = {k: _tuples(v) for k, v in node.items()}
    if out and all(isinstance(k, int) for k in out):
        return tuple(out[i] for i in range(len(out)))
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _init(leaves: Tuple[Leaf, ...], root):
    tree: Dict = {}
    for i, leaf in enumerate(leaves):
        key = _leaf_key(root, i)
        if leaf.stacked:
            x = jax.vmap(lambda l: _draw(key, leaf, l))(jnp.arange(leaf.stacked))
        else:
            x = _draw(key, leaf, 0)
        _set(tree, leaf.path, x)
    return _tuples(tree)


def init_params(leaves: List[Leaf], seed: int):
    """The whole served parameter tree of ``leaves`` (a family's
    ``layout``), on the default device."""
    return _init(tuple(leaves), root_key(seed))


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(leaves: Tuple[Tuple[int, Leaf], ...], root, layer):
    return {leaf.path[2:]: _draw(_leaf_key(root, i), leaf, layer) for i, leaf in leaves}


def layer_weights(leaves: List[Leaf], seed: int, layer: int, group: int = 0) -> Dict[Tuple, jax.Array]:
    """Layer ``layer`` of the stacked group ``("blocks", group)``, keyed by
    each leaf's path below the group."""
    sel = tuple((i, l) for i, l in enumerate(leaves) if l.stacked and l.path[:2] == ("blocks", group))
    return _layer(sel, root_key(seed), jnp.int32(layer))


@functools.partial(jax.jit, static_argnums=(0,))
def _top(leaves: Tuple[Tuple[int, Leaf], ...], root):
    return {leaf.path: _draw(_leaf_key(root, i), leaf, 0) for i, leaf in leaves}


def top_weights(leaves: List[Leaf], seed: int) -> Dict[Tuple, jax.Array]:
    """The leaves outside the layers: embedding, LM head, final norm."""
    sel = tuple((i, l) for i, l in enumerate(leaves) if not l.stacked)
    return _top(sel, root_key(seed))
