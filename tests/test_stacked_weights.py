"""Serving steps read each layer's GEMM weights in place from the stacked
parameters (``ops.LayerWeight``); training scans the stack as before.

The in-place path is compared with the path that scans the stacked
parameters as ``xs`` (``transformer._IN_PLACE_MODES`` emptied), on the
Pallas interpreter, whose stacked entry masks the ragged K and N edges that
the tiny widths here (K = 64 or 200, N = 200 at 128-wide tiles) produce.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import ARCHS
from repro.kernels import ops
from repro.models import api, transformer
from repro.serve import ContinuousEngine, Request

KEY = jax.random.key(0)
BACKEND = "pallas_interpret"
# The layer GEMM sites of one attention + MLP block: q, k, v, o, up, gate,
# down.
SITES_PER_BLOCK = 7


def _cfg(qkv_bias: bool, parallel_block: bool):
    cfg = ARCHS["chatglm3-6b"].reduced()
    return dataclasses.replace(
        cfg, n_layers=2, d_ff=200, qkv_bias=qkv_bias,
        parallel_block=parallel_block,
    )


def _gemm_calls():
    """``gemm.calls`` as ``{(backend, b): count}``."""
    out = {}
    for key, v in obs.snapshot()["counters"].get("gemm.calls", {}).items():
        labels = dict(p.split("=", 1) for p in key.split(","))
        k = (labels["backend"], labels["b"])
        out[k] = out.get(k, 0) + v
    return out


def _serving_steps(cfg, params):
    """One bucketed prefill of two ragged rows, then one slot-indexed decode
    step at each row's next position."""
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    lengths = jnp.array([12, 9], jnp.int32)
    logits_p, caches_p = api.prefill_bucketed(
        cfg, params, tokens, lengths, jnp.float32, backend=BACKEND
    )
    tok = jnp.argmax(logits_p, axis=-1).astype(jnp.int32)[:, None]
    logits_d, caches_d = api.decode_at(
        cfg, params, tok, caches_p, lengths, backend=BACKEND
    )
    return logits_p, caches_p, logits_d, caches_d


@pytest.mark.parametrize("parallel_block", [False, True], ids=["seq", "par"])
@pytest.mark.parametrize("qkv_bias", [False, True], ids=["nobias", "bias"])
def test_serving_steps_match_the_sliced_scan(monkeypatch, qkv_bias, parallel_block):
    cfg = _cfg(qkv_bias, parallel_block)
    params = api.init_params(cfg, KEY)

    got = _serving_steps(cfg, params)
    in_place = _gemm_calls()
    obs.reset()
    monkeypatch.setattr(transformer, "_IN_PLACE_MODES", frozenset())
    want = _serving_steps(cfg, params)
    sliced = _gemm_calls()

    # Same operands, tiles and fp32 accumulation order: bitwise equal.
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # prefill and decode each trace every layer GEMM site once
    sites = 2 * SITES_PER_BLOCK * len(cfg.pattern)
    assert in_place == {(BACKEND, "stacked"): sites}
    assert sliced == {(BACKEND, "array"): sites}


def test_training_reads_no_weight_in_place():
    cfg = _cfg(qkv_bias=True, parallel_block=False)
    params = api.init_params(cfg, KEY)
    tokens = jax.random.randint(jax.random.key(2), (2, 16), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    grads = jax.grad(
        lambda p: api.loss_fn(cfg, p, batch, backend=BACKEND)
    )(params)
    assert all(np.isfinite(np.asarray(g, np.float32)).all()
               for g in jax.tree.leaves(grads))
    calls = _gemm_calls()
    assert calls and set(calls) == {(BACKEND, "array")}


def test_engine_decode_reads_every_layer_gemm_in_place(monkeypatch):
    """The continuous engine on ``pallas`` (pointed at the interpreter on
    this host): its decode program traces every layer GEMM site once, each
    reading its weight in place, and a whole serve runs no other kind."""
    from repro.serve.cache import init_slot_caches

    monkeypatch.setitem(
        ops._REGISTRY, "pallas",
        dataclasses.replace(ops._REGISTRY[BACKEND], name="pallas"),
    )
    cfg = _cfg(qkv_bias=True, parallel_block=False)
    params = api.init_params(cfg, KEY)

    def engine():
        return ContinuousEngine(
            cfg=cfg, params=params, n_slots=2, max_len=32,
            cache_dtype=jnp.float32,
        )

    eng = engine()
    i32 = jnp.int32
    eng._decode.lower(
        params, init_slot_caches(cfg, 2, 32, eng.cache_dtype),
        jnp.zeros((2, 1), i32), jnp.zeros((2,), i32), jnp.ones((2,), bool),
        jax.random.key(0),
    )
    assert _gemm_calls() == {
        ("pallas", "stacked"): SITES_PER_BLOCK * len(cfg.pattern)
    }

    obs.reset()
    prompts = jax.random.randint(jax.random.key(3), (3, 9), 0, cfg.vocab)
    requests = [
        Request(rid=i, prompt=[int(t) for t in prompts[i]], max_new_tokens=4)
        for i in range(3)
    ]
    report = engine().serve(requests)
    assert all(len(report.outputs[r.rid]) == 4 for r in requests)
    assert set(_gemm_calls()) == {("pallas", "stacked")}
