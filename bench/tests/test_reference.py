"""The float32 reference against the program's own prefill-then-decode
logits, at a reduced width on the CPU, with the program in float32 on its
``xla`` backend so that what differs is the arithmetic of the two
descriptions, not the precision."""

import dataclasses
import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_config
from lib import weights
from reference import dense

# Both sides compute in float32; they differ in summation order and in the
# RMSNorm epsilon (the program's 1e-6 against the published 1e-5), which
# moves logits by ~1e-5 of their scale. A wrong rotary layout, norm or block
# arrangement moves them by order 1.
TOL = 1e-3


def _program_logits(cfgd, arch, changes, seed, tokens, n_prompt):
    from repro.configs import get_config
    from repro.models import api

    cfg = dataclasses.replace(get_config(arch), **changes)
    params = weights.init_params(dense.layout(cfgd), seed)
    max_len = len(tokens) + 8
    logits, caches = api.prefill(cfg, params, {"tokens": jnp.asarray(tokens[None, :n_prompt])}, max_len,
                                 cache_dtype=jnp.float32)
    out = [np.asarray(logits[0])]
    for p in range(n_prompt, len(tokens)):
        logits, caches = api.decode(cfg, params, jnp.asarray(tokens[None, p:p + 1]), caches,
                                    jnp.int32(p))
        out.append(np.asarray(logits[0]))
    return np.stack(out)


@pytest.mark.parametrize("base", ["chatglm3-6b", "stablelm-12b.pp4"])
def test_reference_matches_prefill_then_decode(base):
    cfg = tiny_config(base, param_dtype="float32")
    cfgd = cfg["config"]
    seed = 2 ** 32 + 99
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfgd["vocab"], 40).astype(np.int32)
    n_prompt = 29
    got = _program_logits(cfgd, cfg["arch"], cfg["changes"], seed, tokens, n_prompt)
    ref = dense.logits_at(cfgd, seed, [tokens], [n_prompt - 1], pad_to=16)[0]
    assert ref.shape == got.shape
    err = np.max(np.abs(ref - got)) / np.max(np.abs(ref))
    assert err < TOL, err


def test_control_is_not_the_reference():
    cfgd = tiny_config("chatglm3-6b")["config"]
    toks = np.arange(30, dtype=np.int32) % cfgd["vocab"]
    a = dense.logits_at(cfgd, 5, [toks], [10], pad_to=16)[0]
    b = dense.logits_at(cfgd, 5, [toks], [10], quant="fp8", pad_to=16)[0]
    rel = np.max(np.abs(a - b)) / np.max(np.abs(a))
    assert 1e-3 < rel < 0.5, rel


def test_layer_regeneration_is_bitwise():
    """The reference's layer-at-a-time weights are the served ones."""
    cfgd = tiny_config("stablelm-12b.pp4")["config"]
    seed = 2 ** 33 + 1
    leaves = dense.layout(cfgd)
    params = weights.init_params(leaves, seed)
    for layer in range(cfgd["n_layers"]):
        w = weights.layer_weights(leaves, seed, layer)
        blk = params["blocks"][0]
        for path, x in w.items():
            node = blk
            for p in path:
                node = node[p]
            assert np.array_equal(np.asarray(node[layer]), np.asarray(x)), path
    top = weights.top_weights(leaves, seed)
    assert np.array_equal(np.asarray(top[("lm_head",)]), np.asarray(params["lm_head"]))


# The leaves as the dense layout gave them before it moved into
# reference/dense.py, in order (so every leaf keeps its key): path, shape,
# dtype, kind, fan-in, and whether it is stacked over the layers.
def _glm_leaves(d, q, kv, ff, v):
    blk = ("blocks", 0)
    return [(("embed", "table"), (v, d), "bfloat16", "embed", d, False),
            (("lm_head",), (v, d), "bfloat16", "dense", d, False),
            (("final_norm", "scale"), (d,), "float32", "scale", d, False),
            (blk + ("norm_mixer", "scale"), (d,), "float32", "scale", d, True),
            (blk + ("norm_ffn", "scale"), (d,), "float32", "scale", d, True),
            (blk + ("attn", "wq", "w"), (d, q), "bfloat16", "dense", d, True),
            (blk + ("attn", "wk", "w"), (d, kv), "bfloat16", "dense", d, True),
            (blk + ("attn", "wv", "w"), (d, kv), "bfloat16", "dense", d, True),
            (blk + ("attn", "wo", "w"), (q, d), "bfloat16", "dense", q, True),
            (blk + ("mlp", "w_up"), (d, ff), "bfloat16", "dense", d, True),
            (blk + ("mlp", "w_gate"), (d, ff), "bfloat16", "dense", d, True),
            (blk + ("mlp", "w_down"), (ff, d), "bfloat16", "dense", ff, True),
            (blk + ("attn", "wq", "b"), (q,), "bfloat16", "bias", d, True),
            (blk + ("attn", "wk", "b"), (kv,), "bfloat16", "bias", d, True),
            (blk + ("attn", "wv", "b"), (kv,), "bfloat16", "bias", d, True)]


def _stablelm_leaves(d, q, kv, ff, v):
    blk = ("blocks", 0)
    out = [(("embed", "table"), (v, d), "bfloat16", "embed", d, False),
           (("lm_head",), (v, d), "bfloat16", "dense", d, False),
           (("final_norm", "scale"), (d,), "float32", "scale", d, False),
           (("final_norm", "bias"), (d,), "float32", "bias", d, False)]
    for norm in ("norm_mixer", "norm_ffn"):
        out += [(blk + (norm, "scale"), (d,), "float32", "scale", d, True),
                (blk + (norm, "bias"), (d,), "float32", "bias", d, True)]
    return out + _glm_leaves(d, q, kv, ff, v)[5:12]


# sha256 over every leaf's path and bytes of the reduced configurations'
# weights from seed 2**40 + 3, as the parent drew them
WEIGHTS_SHA = {
    "chatglm3-6b": "60fe49bbeb91cbe5629ce86485512c844c89eb15768aa545bba4df8b52d88101",
    "stablelm-12b.pp4": "3f72b4c101b64b8464c0b32f8de5ce85aea07d4853d50265cd5d0613f0d18ecf",
}


@pytest.mark.parametrize("base,leaves,widths", [
    ("chatglm3-6b", _glm_leaves, (4096, 4096, 256, 13696, 65024)),
    ("stablelm-12b.pp4", _stablelm_leaves, (5120, 5120, 1280, 13824, 100352)),
])
def test_layout_and_weights_are_pinned(base, leaves, widths):
    import json
    import os

    import jax

    from conftest import BENCH

    with open(os.path.join(BENCH, "configs", base + ".json")) as f:
        cfgd = json.load(f)["config"]
    got = dense.layout(cfgd)
    assert [tuple(x[:5]) + (bool(x.stacked),) for x in got] == leaves(*widths)
    assert {x.stacked for x in got if x.stacked} == {cfgd["n_layers"]}
    tiny = tiny_config(base)["config"]
    params = weights.init_params(dense.layout(tiny), 2 ** 40 + 3)
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, x in sorted(flat, key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA[base]
