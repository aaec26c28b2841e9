"""Decoder-only LM assembly: heterogeneous block programs under one layer-scan.

An :class:`~repro.configs.base.ArchConfig` declares a repeating *period* of
:class:`BlockDef` layers (e.g. gemma2: ``(local, global)``; jamba:
``(attn+moe, mamba+mlp, mamba+moe, ...)``). Parameters for each period
position are stacked over ``n_periods`` and the forward pass is a single
``lax.scan`` over periods — keeping the HLO (and compile time) independent of
depth, which is what makes the 40-cell x 2-mesh dry-run tractable.

Modes:
* ``train``   — full sequence, no caches, returns final hidden states.
* ``prefill`` — full sequence, fills and returns per-layer caches.
* ``decode``  — one token against the caches.
* ``chunk``   — chunked prefill: a fixed-width window of prompt tokens
  appended at per-row ``positions`` (attention-only patterns).

Caches are per-period-position stacked pytrees (KVCache / MambaState /
MLSTMState / SLSTMState), scanned alongside the parameters.

The serving modes (``prefill``, ``decode``, ``chunk``) scan the layer index
instead of the parameters: each block gets ``ops.LayerWeight`` views of the
stacked weights, which its GEMMs read in place, and every other parameter is
sliced (``ops.materialize``). ``train`` scans the stacked
parameters as ``xs``: under ``jax.grad`` a closed-over stack would turn each
layer's weight gradient into a scatter-add into a full ``[L, ...]`` zero.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, BlockDef
from repro.kernels import ops
from . import attention as attn_mod
from . import mamba as mamba_mod
from . import xlstm as xlstm_mod
from .attention import KVCache
from .layers import (
    Initializer,
    embedding_init,
    layernorm,
    layernorm_init,
    mlp_apply,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
    softcap,
)
from .moe import moe_apply, moe_init

__all__ = [
    "init_lm_params",
    "lm_forward",
    "lm_logits",
    "lm_loss",
    "init_caches",
]


def _norm_init(cfg: ArchConfig, d: int):
    return rmsnorm_init(d) if cfg.norm == "rmsnorm" else layernorm_init(d)


def _norm(cfg: ArchConfig, p, x):
    return rmsnorm(p, x) if cfg.norm == "rmsnorm" else layernorm(p, x)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _block_init(cfg: ArchConfig, bd: BlockDef, key) -> Dict[str, Any]:
    init = Initializer(dtype=jnp.dtype(cfg.param_dtype))
    keys = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm_mixer": _norm_init(cfg, cfg.d_model)}
    if bd.mixer in ("attn", "attn_local"):
        p["attn"] = attn_mod.attention_init(
            keys[0],
            cfg.d_model,
            cfg.n_heads,
            cfg.n_kv,
            cfg.head_dim_,
            init,
            qkv_bias=cfg.qkv_bias,
        )
    elif bd.mixer == "mamba":
        assert cfg.mamba is not None
        p["mamba"] = mamba_mod.mamba_init(
            keys[0],
            cfg.d_model,
            expand=cfg.mamba.expand,
            d_state=cfg.mamba.d_state,
            d_conv=cfg.mamba.d_conv,
            init=init,
        )
    elif bd.mixer == "mlstm":
        p["mlstm"] = xlstm_mod.mlstm_init(keys[0], cfg.d_model, cfg.n_heads, init=init)
    elif bd.mixer == "slstm":
        p["slstm"] = xlstm_mod.slstm_init(keys[0], cfg.d_model, cfg.n_heads, init=init)
    elif bd.mixer != "none":
        raise ValueError(f"unknown mixer {bd.mixer!r}")

    if bd.ffn == "mlp":
        p["norm_ffn"] = _norm_init(cfg, cfg.d_model)
        p["mlp"] = mlp_init(keys[1], cfg.d_model, cfg.d_ff, init)
    elif bd.ffn == "moe":
        assert cfg.moe is not None
        p["norm_ffn"] = _norm_init(cfg, cfg.d_model)
        p["moe"] = moe_init(
            keys[1],
            cfg.d_model,
            cfg.moe.d_ff_expert,
            cfg.moe.n_experts,
            init,
            n_shared=cfg.moe.n_shared,
            d_ff_shared=cfg.moe.d_ff_shared,
        )
    elif bd.ffn != "none":
        raise ValueError(f"unknown ffn {bd.ffn!r}")
    return p


def init_lm_params(cfg: ArchConfig, key: jax.Array) -> Dict[str, Any]:
    init = Initializer(dtype=jnp.dtype(cfg.param_dtype))
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": embedding_init(k_embed, cfg.vocab, cfg.d_model, init),
        "final_norm": _norm_init(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init(k_head, (cfg.vocab, cfg.d_model))
    # Stack each period position over n_periods via vmap of the block init.
    blocks = []
    for pos, bd in enumerate(cfg.pattern):
        keys = jax.random.split(jax.random.fold_in(k_blocks, pos), cfg.n_periods)
        blocks.append(jax.vmap(lambda k, bd=bd: _block_init(cfg, bd, k))(keys))
    params["blocks"] = tuple(blocks)
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def _block_cache(cfg: ArchConfig, bd: BlockDef, batch: int, max_len: int, dtype):
    if bd.mixer in ("attn", "attn_local"):
        return KVCache.zeros(batch, max_len, cfg.n_kv, cfg.head_dim_, dtype)
    if bd.mixer == "mamba":
        return mamba_mod.MambaState.zeros(
            batch, cfg.mamba.expand * cfg.d_model, cfg.mamba.d_state,
            cfg.mamba.d_conv, dtype,
        )
    if bd.mixer == "mlstm":
        return xlstm_mod.MLSTMState.zeros(batch, cfg.n_heads, cfg.head_dim_)
    if bd.mixer == "slstm":
        return xlstm_mod.SLSTMState.zeros(batch, cfg.n_heads, cfg.head_dim_)
    return None


def init_caches(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked per-period caches, one entry per pattern position (or None)."""
    caches = []
    for bd in cfg.pattern:
        c = _block_cache(cfg, bd, batch, max_len, dtype)
        if c is None:
            caches.append(None)
        else:
            caches.append(
                jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (cfg.n_periods,) + x.shape
                    ),
                    c,
                )
            )
    return tuple(caches)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _block_apply(
    cfg: ArchConfig,
    bd: BlockDef,
    p,
    x: jax.Array,
    *,
    positions,
    cache,
    backend=None,
    chunk=False,
):
    """One layer. Returns (x, new_cache, aux_loss).

    ``p``'s leaves are arrays or, in the serving modes, ``ops.LayerWeight``
    views: attention, MLP and MoE pass them to their GEMMs, and everything
    else takes the slice (``ops.materialize``, the identity on arrays)."""
    from .layers import role_backend

    aux = jnp.zeros((), jnp.float32)
    new_cache = cache
    h = _norm(cfg, ops.materialize(p["norm_mixer"]), x)
    mixer_out = None
    stream = x  # the residual stream after the mixer's skip connection
    # attention / mlp / moe resolve their own precision-policy roles inside;
    # the recurrent mixers take a plain backend name resolved here.
    mixer_be = role_backend(backend, "mixer")
    if chunk and bd.mixer not in ("attn", "attn_local", "none"):
        # Recurrent state can't resume mid-prompt from a cache scatter; the
        # engine gates chunked prefill to attention-only patterns.
        raise NotImplementedError("chunked prefill requires attention mixers")
    # Recurrent mixers take their weights as slices, as in training.
    rec_p = (
        ops.materialize(p[bd.mixer])
        if bd.mixer in ("mamba", "mlstm", "slstm") else None
    )
    if bd.mixer in ("attn", "attn_local"):
        # The mixer's residual add rides the output projection's epilogue:
        # attention returns x + attn(h) in one writeback.
        stream, new_cache = attn_mod.attention_apply(
            p["attn"],
            h,
            n_heads=cfg.n_heads,
            n_kv=cfg.n_kv,
            head_dim=cfg.head_dim_,
            positions=positions,
            rotary_frac=cfg.rope_frac,
            rope_theta=cfg.rope_theta,
            window=cfg.window if bd.mixer == "attn_local" else None,
            attn_softcap=cfg.attn_softcap,
            cache=cache,
            q_chunk=cfg.q_chunk,
            kv_chunk=cfg.kv_chunk,
            seq_shard=cfg.attn_seq_shard,
            backend=backend,
            residual=x,
            chunk=chunk,
        )
        mixer_out = stream  # non-None marks "this block has a mixer"
    elif bd.mixer == "mamba":
        if cache is not None and x.shape[1] == 1:
            mixer_out, new_cache = mamba_mod.mamba_decode_step(
                rec_p, h, cache, backend=mixer_be
            )
        else:
            mixer_out, state = mamba_mod.mamba_apply(
                rec_p, h, chunk=cfg.scan_chunk, backend=mixer_be,
                return_state=True,
            )
            if cache is not None:
                new_cache = state  # prefill installs the post-sequence state
    elif bd.mixer == "mlstm":
        if cache is not None and x.shape[1] == 1:
            mixer_out, new_cache = xlstm_mod.mlstm_decode_step(
                rec_p, h, cache, n_heads=cfg.n_heads, backend=mixer_be
            )
        else:
            mixer_out, state = xlstm_mod.mlstm_apply(
                rec_p, h, n_heads=cfg.n_heads, chunk=cfg.scan_chunk,
                backend=mixer_be, return_state=True,
            )
            if cache is not None:
                new_cache = state
    elif bd.mixer == "slstm":
        if cache is not None and x.shape[1] == 1:
            mixer_out, new_cache = xlstm_mod.slstm_decode_step(
                rec_p, h, cache, n_heads=cfg.n_heads, backend=mixer_be
            )
        else:
            mixer_out, state = xlstm_mod.slstm_apply(
                rec_p, h, n_heads=cfg.n_heads, backend=mixer_be,
                return_state=True,
            )
            if cache is not None:
                new_cache = state

    if mixer_out is not None and stream is x:
        # Recurrent mixers (mamba/xlstm) keep a plain residual add: their
        # output projections live inside the mixer modules, behind gating.
        stream = x + mixer_out

    if cfg.parallel_block and bd.ffn != "none" and mixer_out is not None:
        # StableLM-2 style: attn and MLP read the same normed input and share
        # one residual add — x + mixer_out (already on `stream`) fuses into
        # the MLP down projection's writeback.
        return (
            mlp_apply(p["mlp"], h, backend=backend, residual=stream),
            new_cache,
            aux,
        )

    if bd.ffn == "mlp":
        # Pre-norm FFN with its skip connection fused into the down GEMM.
        stream = mlp_apply(
            p["mlp"], _norm(cfg, ops.materialize(p["norm_ffn"]), stream),
            backend=backend, residual=stream,
        )
    elif bd.ffn == "moe":
        y, aux = moe_apply(
            p["moe"],
            _norm(cfg, ops.materialize(p["norm_ffn"]), stream),
            n_experts=cfg.moe.n_experts,
            top_k=cfg.moe.top_k,
            capacity_factor=cfg.moe.capacity_factor,
            dispatch=cfg.moe.dispatch,
            group_size=cfg.moe.group_size,
            dropless=cfg.moe.dropless,
            backend=backend,
        )
        # The MoE output is a scatter-weighted expert combine (or, with a
        # shared expert, already carries the routed sum via a residual
        # epilogue inside moe_apply) — not a bare GEMM writeback, so its
        # block-residual add stays a plain op.
        stream = stream + y
    return stream, new_cache, aux


# The modes that scan the layer index and read weights in place (module doc).
_IN_PLACE_MODES = frozenset({"prefill", "decode", "chunk"})


def lm_forward(
    params,
    tokens: jax.Array,
    cfg: ArchConfig,
    *,
    mode: str = "train",
    caches=None,
    positions: Optional[jax.Array] = None,
    extra_embeds: Optional[jax.Array] = None,
    backend: Optional[str] = None,
):
    """Run the backbone. tokens: [B, S] -> hidden [B, S(+img), D].

    Returns ``(hidden, new_caches, aux_loss)``. ``extra_embeds`` (VLM) are
    prepended to the token embeddings before the block stack.
    """
    x = params["embed"]["table"][tokens]  # vocab-sharded gather
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    n_pos = len(cfg.pattern)
    have_caches = caches is not None
    chunk = mode == "chunk"  # chunked prefill: scatter-append at `positions`
    in_place = mode in _IN_PLACE_MODES

    def period_body(carry, xs):
        x, aux = carry
        if in_place:
            layer, xs = xs[0], xs[1:]
            block_params = tuple(
                jax.tree.map(lambda w: ops.LayerWeight(w, layer), blk)
                for blk in params["blocks"]
            )
        else:
            block_params, xs = xs[:n_pos], xs[n_pos:]
        block_caches = xs if have_caches else (None,) * n_pos
        new_caches = []
        for pos, bd in enumerate(cfg.pattern):
            cache_in = block_caches[pos]
            placeholder = None
            if have_caches and isinstance(cache_in, jax.Array):
                placeholder, cache_in = cache_in, None  # zero-size stand-in
            x, nc, a = _block_apply(
                cfg,
                bd,
                block_params[pos],
                x,
                positions=positions,
                cache=cache_in,
                backend=backend,
                chunk=chunk,
            )
            aux = aux + a
            new_caches.append(nc if nc is not None else placeholder)
        return (x, aux), (tuple(new_caches) if have_caches else None)

    body = period_body
    if cfg.remat and mode == "train" and cfg.remat_policy != "none":
        if cfg.remat_policy == "dots":
            # Save GEMM outputs; recompute only the cheap elementwise chains
            # in the backward pass — trades HBM (we have headroom in every
            # train cell) for a ~25% FLOP cut vs full remat (§Perf).
            body = jax.checkpoint(
                period_body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        else:
            body = jax.checkpoint(period_body)

    if in_place:
        xs = (jnp.arange(cfg.n_periods, dtype=jnp.int32),)
    else:
        xs = tuple(params["blocks"])
    if have_caches:
        xs = xs + tuple(
            c if c is not None else _none_stack(cfg.n_periods) for c in caches
        )
    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    x = _norm(cfg, params["final_norm"], x)
    return x, new_caches, aux


def _none_stack(n: int):
    return jnp.zeros((n, 0), jnp.float32)  # zero-size array: free to scan


def lm_logits(params, hidden: jax.Array, cfg: ArchConfig) -> jax.Array:
    table = params["embed"]["table"] if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum(
        "bsd,vd->bsv", hidden, table, preferred_element_type=jnp.float32
    )
    return softcap(logits, cfg.final_softcap)


def _chunked_ce(
    params,
    hidden: jax.Array,
    labels: jax.Array,
    cfg: ArchConfig,
    loss_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Chunked softmax cross-entropy (the [B,S,V] logits tensor never exists:
    at 152k vocab x 1M tokens it would be ~0.6 PB)."""
    b, s, d = hidden.shape
    table = params["embed"]["table"] if cfg.tie_embeddings else params["lm_head"]
    ck = min(cfg.loss_chunk, s)
    while s % ck:
        ck -= 1
    nc = s // ck
    h_c = hidden.reshape(b, nc, ck, d).transpose(1, 0, 2, 3)
    y_c = labels.reshape(b, nc, ck).transpose(1, 0, 2)
    m_c = (
        loss_mask.reshape(b, nc, ck).transpose(1, 0, 2).astype(jnp.float32)
        if loss_mask is not None
        else jnp.ones((nc, b, ck), jnp.float32)
    )

    def chunk_ce(carry, inp):
        h, y, m = inp
        logits = jnp.einsum(
            "bsd,vd->bsv", h, table, preferred_element_type=jnp.float32
        )
        logits = softcap(logits, cfg.final_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        ce = (lse - gold) * m
        return (carry[0] + ce.sum(), carry[1] + m.sum()), None

    (total, count), _ = jax.lax.scan(
        chunk_ce, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (h_c, y_c, m_c),
    )
    return total / jnp.maximum(count, 1.0)


def lm_loss(
    params,
    tokens: jax.Array,
    labels: jax.Array,
    cfg: ArchConfig,
    *,
    loss_mask: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    hidden, _, aux = lm_forward(params, tokens, cfg, mode="train", backend=backend)
    if cfg.n_img_tokens:
        hidden = hidden[:, cfg.n_img_tokens :]
    return _chunked_ce(params, hidden, labels, cfg, loss_mask) + 0.01 * aux


# (parameter accounting lives in repro.models.api — family-dispatched)
