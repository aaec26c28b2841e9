"""The float32 reference against the program's own prefill-then-decode
logits, at a reduced width on the CPU, with the program in float32 on its
``xla`` backend so that what differs is the arithmetic of the two
descriptions, not the precision."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_config
from lib import weights

# Both sides compute in float32; they differ in summation order and in the
# RMSNorm epsilon (the program's 1e-6 against the published 1e-5), which
# moves logits by ~1e-5 of their scale. A wrong rotary layout, norm or block
# arrangement moves them by order 1.
TOL = 1e-3


def _program_logits(cfgd, arch, changes, seed, tokens, n_prompt):
    from repro.configs import get_config
    from repro.models import api

    cfg = dataclasses.replace(get_config(arch), **changes)
    params = weights.init_params(cfgd, seed)
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
    from reference import dense

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
    from reference import dense

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
    params = weights.init_params(cfgd, seed)
    for layer in range(cfgd["n_layers"]):
        w = weights.layer_weights(cfgd, seed, layer)
        blk = params["blocks"][0]
        for path, x in w.items():
            node = blk
            for p in path:
                node = node[p]
            assert np.array_equal(np.asarray(node[layer]), np.asarray(x)), path
    top = weights.top_weights(cfgd, seed)
    assert np.array_equal(np.asarray(top[("lm_head",)]), np.asarray(params["lm_head"]))
