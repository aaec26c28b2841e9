"""Family-dispatched model API: one surface for all 10 architectures.

Batch dicts (matching ``launch.dryrun.input_specs``):

* LM families (dense/moe/hybrid/ssm): ``{"tokens", "labels"}``
* vlm:   ``{"tokens", "labels", "patch_embeds"}``
* audio: ``{"frames", "tokens", "labels"}``

Decode state is ``(caches, pos)`` where ``caches`` is the family's stacked
cache pytree and ``pos`` the current sequence position (int32 scalar).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import encdec as encdec_mod
from . import transformer as tf_mod
from . import vlm as vlm_mod
from .attention import KVCache

__all__ = [
    "init_params",
    "input_specs",
    "loss_fn",
    "prefill",
    "prefill_bucketed",
    "prefill_chunk",
    "decode",
    "decode_at",
    "init_state",
    "param_count",
    "active_param_count",
]


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ArchConfig, key: jax.Array):
    """Random parameters for ``cfg`` from ``key``.

    Jitted with ``cfg`` static so XLA fuses each weight's draw, scale and
    cast: run eagerly, every stacked full-depth weight would first exist as
    an f32 draw and again as its scaled f32 copy before the cast to the
    parameter dtype, which does not fit one chip's HBM at 6B scale.
    """
    if cfg.family == "audio":
        return encdec_mod.init_encdec_params(cfg, key)
    if cfg.family == "vlm":
        return vlm_mod.init_vlm_params(cfg, key)
    return tf_mod.init_lm_params(cfg, key)


def input_specs(
    cfg: ArchConfig, *, batch: int, seq: int, kind: str = "train"
) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of one cell.

    The single owner of the per-family batch layout (tokens/labels,
    vlm ``patch_embeds``, audio ``frames``; ``kind="decode"`` is one new
    token against caches of length ``seq``). ``launch.dryrun`` and the
    autotuner's workload harvest (``repro.tune.capture``) both build their
    abstract batches here — it lives in this module, not the dry-run
    launcher, because importing the launcher force-sets the host device
    count as an import side effect.
    """
    i32 = jnp.int32
    if kind in ("train", "prefill"):
        s_text = seq - cfg.n_img_tokens if cfg.family == "vlm" else seq
        specs: Dict[str, Any] = {
            "tokens": jax.ShapeDtypeStruct((batch, s_text), i32),
        }
        if kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((batch, s_text), i32)
        if cfg.family == "vlm":
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (batch, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16
            )
        if cfg.family == "audio":
            specs["frames"] = jax.ShapeDtypeStruct(
                (batch, cfg.enc_seq, cfg.d_model), jnp.bfloat16
            )
        return specs
    return {"token": jax.ShapeDtypeStruct((batch, 1), i32)}


def loss_fn(
    cfg: ArchConfig, params, batch: Dict[str, jax.Array], *, backend=None
) -> jax.Array:
    """Training loss. ``backend`` is a matmul backend name or a
    :class:`repro.quant.policy.PrecisionPolicy` (role-resolved per layer);
    gradients through quantized backends run full-precision by registry rule,
    so the fp32 master path of the train step is untouched by any policy."""
    if cfg.family == "audio":
        return encdec_mod.encdec_loss(
            params, batch["frames"], batch["tokens"], batch["labels"], cfg,
            backend=backend,
        )
    if cfg.family == "vlm":
        return vlm_mod.vlm_loss(
            params, batch["tokens"], batch["patch_embeds"], batch["labels"],
            cfg, backend=backend,
        )
    return tf_mod.lm_loss(
        params, batch["tokens"], batch["labels"], cfg, backend=backend
    )


def init_state(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Decode caches sized for ``max_len`` total positions."""
    if cfg.family == "audio":
        return encdec_mod.init_decoder_caches(cfg, batch, max_len, dtype)
    return tf_mod.init_caches(cfg, batch, max_len, dtype)


def prefill(
    cfg: ArchConfig, params, batch: Dict[str, jax.Array], max_len: int,
    cache_dtype=jnp.bfloat16, *, backend=None,
) -> Tuple[jax.Array, Any]:
    """Process the full prompt; return (last-token logits [B,V], caches).

    ``backend`` is a matmul backend name or a
    :class:`repro.quant.policy.PrecisionPolicy` (role-resolved per layer) —
    the serving-side entry points accept the same precision plumbing as
    :func:`loss_fn`."""
    if cfg.family == "audio":
        enc_out = encdec_mod.encode(params, batch["frames"], cfg, backend=backend)
        caches = encdec_mod.init_decoder_caches(
            cfg, batch["tokens"].shape[0], max_len, cache_dtype
        )
        hidden, caches = encdec_mod.decoder_forward(
            params, batch["tokens"], cfg, enc_out=enc_out, caches=caches,
            mode="prefill", backend=backend,
        )
        logits = jnp.einsum(
            "bd,vd->bv", hidden[:, -1], params["embed"]["table"],
            preferred_element_type=jnp.float32,
        )
        return logits, caches

    b = batch["tokens"].shape[0]
    caches = tf_mod.init_caches(cfg, b, max_len, cache_dtype)
    extra = None
    if cfg.family == "vlm":
        extra = vlm_mod.project_image(params, batch["patch_embeds"])
    hidden, caches, _ = tf_mod.lm_forward(
        params, batch["tokens"], cfg, mode="prefill", caches=caches,
        extra_embeds=extra, backend=backend,
    )
    logits = tf_mod.lm_logits(params, hidden[:, -1:], cfg)[:, 0]
    return logits, caches


def decode(
    cfg: ArchConfig, params, token: jax.Array, caches, pos: jax.Array,
    *, backend=None,
) -> Tuple[jax.Array, Any]:
    """One decode step. token: [B, 1] -> (logits [B, V], new caches)."""
    if cfg.family == "audio":
        hidden, caches = encdec_mod.decoder_forward(
            params, token, cfg, caches=caches, mode="decode", backend=backend
        )
        logits = jnp.einsum(
            "bd,vd->bv", hidden[:, 0], params["embed"]["table"],
            preferred_element_type=jnp.float32,
        )
        return logits, caches
    b = token.shape[0]
    positions = jnp.broadcast_to(pos.astype(jnp.int32), (b, 1))
    hidden, caches, _ = tf_mod.lm_forward(
        params, token, cfg, mode="decode", caches=caches, positions=positions,
        backend=backend,
    )
    logits = tf_mod.lm_logits(params, hidden, cfg)[:, 0]
    return logits, caches


def prefill_bucketed(
    cfg: ArchConfig,
    params,
    tokens: jax.Array,
    lengths: jax.Array,
    cache_dtype=jnp.bfloat16,
    *,
    backend=None,
) -> Tuple[jax.Array, Any]:
    """Prefill a right-padded prompt bucket: tokens [B, Lb], lengths [B].

    Rows shorter than the bucket are right-padded; causal attention makes the
    pad positions invisible to every real token, so the returned logits — read
    at each row's ``lengths[b] - 1`` — are exactly the unpadded prefill
    logits. The returned caches span the bucket length ``Lb`` (pad K/V beyond
    a row's length is masked out by the per-slot decode mask downstream).

    Token-prompt LM families only (audio needs encoder frames, vlm needs
    image embeddings). Padding flows *through* recurrent state (mamba/
    xlstm), so the serving scheduler uses exact-length buckets there.
    """
    if cfg.family in ("audio", "vlm"):
        raise NotImplementedError(
            f"bucketed prefill: token-prompt LM families only, not {cfg.family}"
        )
    b, lb = tokens.shape
    caches = tf_mod.init_caches(cfg, b, lb, cache_dtype)
    hidden, caches, _ = tf_mod.lm_forward(
        params, tokens, cfg, mode="prefill", caches=caches, backend=backend
    )
    last = hidden[jnp.arange(b), lengths.astype(jnp.int32) - 1]
    logits = tf_mod.lm_logits(params, last[:, None], cfg)[:, 0]
    return logits, caches


def prefill_chunk(
    cfg: ArchConfig,
    params,
    tokens: jax.Array,
    caches,
    offsets: jax.Array,
    last_idx: jax.Array,
    *,
    backend=None,
) -> Tuple[jax.Array, Any]:
    """Advance a prompt-chunk window: tokens [B, C] appended at per-row
    ``offsets[b]``; returns (logits [B, V], caches).

    The resume-from-cached-length prefill entry: row ``b``'s chunk occupies
    absolute positions ``offsets[b] .. offsets[b]+C-1`` of its cache — which
    may start past 0 because earlier chunks (or a reused prefix-cache span)
    already fill positions below ``offsets[b]``. Like :func:`decode_at`,
    ``offsets`` is the source of truth for cache fill, so a cache attached
    from the prefix trie needs no per-layer counter surgery. Rows whose
    prompt is already exhausted pass a sentinel offset ``>= S_max`` — every
    write drops and their lane is pure ballast in the fused step.

    Logits are read at chunk index ``last_idx[b]`` (the row's final prompt
    token when this chunk finishes it; don't-care otherwise — callers mask).
    Token-prompt attention-only LM families; recurrent mixers raise inside
    the forward (state can't resume from a scatter).
    """
    if cfg.family in ("audio", "vlm"):
        raise NotImplementedError(
            f"chunked prefill: token-prompt LM families only, not {cfg.family}"
        )
    b, c = tokens.shape
    offsets = offsets.astype(jnp.int32)
    positions = offsets[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    caches = _with_slot_lengths(caches, offsets)
    hidden, caches, _ = tf_mod.lm_forward(
        params, tokens, cfg, mode="chunk", caches=caches,
        positions=positions, backend=backend,
    )
    last = hidden[jnp.arange(b), last_idx.astype(jnp.int32)]
    logits = tf_mod.lm_logits(params, last[:, None], cfg)[:, 0]
    return logits, caches


def decode_at(
    cfg: ArchConfig, params, token: jax.Array, caches, pos: jax.Array,
    *, backend=None,
) -> Tuple[jax.Array, Any]:
    """Slot-indexed decode step: per-row positions. token [B,1], pos [B].

    Row ``b`` appends its K/V at ``pos[b]`` and attends over its own history
    (``kp <= pos[b]``) — the entry point the continuous-batching pool drives,
    where each batch lane is an independently-positioned request slot. ``pos``
    is the source of truth: per-layer cache fill counters are overwritten from
    it, so a pool whose slots were joined/recycled by scatter stays coherent
    without per-layer bookkeeping.
    """
    if cfg.family == "audio":
        raise NotImplementedError(
            "slot-indexed decode: decoder-only LM families only"
        )
    pos = pos.astype(jnp.int32)
    caches = _with_slot_lengths(caches, pos)
    hidden, caches, _ = tf_mod.lm_forward(
        params, token, cfg, mode="decode", caches=caches,
        positions=pos[:, None], backend=backend,
    )
    logits = tf_mod.lm_logits(params, hidden, cfg)[:, 0]
    return logits, caches


def _with_slot_lengths(caches, pos: jax.Array):
    """Reset every stacked (Quant)KVCache fill counter to the per-slot
    positions."""
    from repro.quant.kvcache import QuantKVCache

    out = []
    for c in caches:
        if isinstance(c, (KVCache, QuantKVCache)):
            n_periods = c.k.shape[0]
            out.append(
                c._replace(
                    length=jnp.broadcast_to(
                        pos[None], (n_periods,) + pos.shape
                    )
                )
            )
        else:
            out.append(c)
    return tuple(out)


def param_count(cfg: ArchConfig) -> int:
    shapes = jax.eval_shape(functools.partial(init_params, cfg), jax.random.key(0))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))


def active_param_count(cfg: ArchConfig) -> int:
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    expert_params_per_layer = 3 * cfg.d_model * cfg.moe.d_ff_expert
    n_moe_layers = cfg.n_periods * sum(1 for b in cfg.pattern if b.ffn == "moe")
    routed_total = n_moe_layers * cfg.moe.n_experts * expert_params_per_layer
    routed_active = n_moe_layers * cfg.moe.top_k * expert_params_per_layer
    return total - routed_total + routed_active
