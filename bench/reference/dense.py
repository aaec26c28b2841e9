"""Plain float32 reference of a dense decoder-only transformer.

Written from the published descriptions of ChatGLM3 (pre-norm RMSNorm,
grouped-query attention with a bias on the Q, K and V projections, rotary
embedding over the first half of each head, SwiGLU MLP, untied LM head) and
StableLM 2 (LayerNorm, one norm feeding attention and MLP side by side,
``x + attn(h) + mlp(h)``, rotary embedding over the first quarter of each
head). Straight ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``: no kernels, no cache, no batching.
The weights are the seed's, regenerated one layer at a time, so it imports
nothing of the program under test and takes nothing the program made.

Where the served program departs from a published description, the
configuration file lists it under ``departures`` and this reference follows
the served arrangement, so that the comparison judges the arithmetic:

* the rotary embedding rotates adjacent channel pairs ``(2i, 2i+1)`` of the
  rotated part. ChatGLM3 does the same; StableLM 2 rotates the two halves of
  it (``rotate_half``), which is the same map on permuted channels;
* StableLM 2's per-head LayerNorm of queries and keys (``qk_layernorm``) is
  absent.

``quant="fp8"`` computes every matmul on operands rounded to float8 e4m3,
scaled per row or column along the contraction: the precision control,
one step below the configuration's bfloat16.

This module is the dense family's one place for its shapes. Besides the
reference (``logits_at``) it gives the harness what the program would
serve (``served``, ``refuses``), the parameter tree (``layout``), the layer
GEMMs that run through the ``opope_gemm`` kernel (``kernel_gemms``), and
the operations and bytes of a token, a prompt, the weights and the KV
cache. A configuration names its family module by ``"reference"``.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from lib import weights as W
from lib.costs import BF16, Gemm
from lib.weights import Leaf

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FP8_MAX = 448.0  # largest finite float8_e4m3fn


# --------------------------------------------------------------------------
# what the program serves, its parameter tree and its costs
# --------------------------------------------------------------------------

SERVED_KEYS = (
    "n_layers", "d_model", "n_heads", "n_kv", "head_dim", "d_ff", "vocab",
    "norm", "rope_frac", "rope_theta", "parallel_block", "tie_embeddings",
    "qkv_bias", "param_dtype",
)


def served(arch) -> Dict:
    """The values the program's ``ArchConfig`` would run, key by key, as the
    configuration file's ``config`` names them."""
    out = {k: getattr(arch, k) for k in SERVED_KEYS if k != "head_dim"}
    out["head_dim"] = arch.head_dim_
    return out


def refuses(arch) -> Optional[str]:
    """Why this reference cannot judge the program's ``ArchConfig``, or
    None."""
    if arch.moe is not None or arch.window or arch.attn_softcap or arch.final_softcap:
        return "mechanisms the dense reference lacks"
    return None


def layout(cfg: Dict) -> List[Leaf]:
    """The served parameter tree, leaf by leaf; a leaf's place in the list
    is its key's index, so new leaves go last."""
    d, hd, ff, v = cfg["d_model"], cfg["head_dim"], cfg["d_ff"], cfg["vocab"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv"] * hd
    w = cfg["param_dtype"]
    ln = cfg["norm"] == "layernorm"
    n = cfg["n_layers"]
    out = [Leaf(("embed", "table"), (v, d), w, "embed", d, 0)]
    if not cfg["tie_embeddings"]:
        out.append(Leaf(("lm_head",), (v, d), w, "dense", d, 0))
    out.append(Leaf(("final_norm", "scale"), (d,), "float32", "scale", d, 0))
    if ln:
        out.append(Leaf(("final_norm", "bias"), (d,), "float32", "bias", d, 0))
    blk = ("blocks", 0)
    for norm in ("norm_mixer", "norm_ffn"):
        out.append(Leaf(blk + (norm, "scale"), (d,), "float32", "scale", d, n))
        if ln:
            out.append(Leaf(blk + (norm, "bias"), (d,), "float32", "bias", d, n))
    out += [
        Leaf(blk + ("attn", "wq", "w"), (d, q), w, "dense", d, n),
        Leaf(blk + ("attn", "wk", "w"), (d, kv), w, "dense", d, n),
        Leaf(blk + ("attn", "wv", "w"), (d, kv), w, "dense", d, n),
        Leaf(blk + ("attn", "wo", "w"), (q, d), w, "dense", q, n),
        Leaf(blk + ("mlp", "w_up"), (d, ff), w, "dense", d, n),
        Leaf(blk + ("mlp", "w_gate"), (d, ff), w, "dense", d, n),
        Leaf(blk + ("mlp", "w_down"), (ff, d), w, "dense", ff, n),
    ]
    if cfg["qkv_bias"]:
        # last, so that the keys of every other leaf stay as they are
        out += [Leaf(blk + ("attn", p, "b"), (m,), w, "bias", d, n)
                for p, m in (("wq", q), ("wk", kv), ("wv", kv))]
    return out


def kernel_gemms(cfg: Dict) -> List[Gemm]:
    """The GEMMs of one decoder layer, all run through ``opope_gemm``: the
    Q, K and V projections, the attention output projection (with the
    residual added in its epilogue), the MLP up projection, the gate
    projection (with ``silu(.) * up`` in its epilogue) and the down
    projection (with the residual). The LM head is counted in the step's
    operations, not among the kernel GEMMs."""
    d, hd, n = cfg["d_model"], cfg["head_dim"], cfg["n_layers"]
    q, kv, ff = cfg["n_heads"] * hd, cfg["n_kv"] * hd, cfg["d_ff"]
    return [
        Gemm("q", d, q, 0, n),
        Gemm("k", d, kv, 0, n),
        Gemm("v", d, kv, 0, n),
        Gemm("o", q, d, 1, n),
        Gemm("up", d, ff, 0, n),
        Gemm("gate", d, ff, 1, n),
        Gemm("down", ff, d, 1, n),
    ]


def _matmul_params_per_layer(cfg: Dict) -> int:
    return sum(g.k * g.n for g in kernel_gemms(cfg))


def token_flops(cfg: Dict, context: int, lm_head: bool) -> float:
    """Operations of one token through the model: every matmul, attention
    over ``context`` positions (scores and the weighted sum), and the LM
    head where the token produces logits."""
    f = 2.0 * cfg["n_layers"] * _matmul_params_per_layer(cfg)
    f += 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * context
    if lm_head:
        f += 2.0 * cfg["vocab"] * cfg["d_model"]
    return f


def prompt_flops(cfg: Dict, plen: int) -> float:
    """A whole prompt prefilled: token ``p`` attends over ``p + 1``
    positions; logits only for the last token."""
    base = 2.0 * cfg["n_layers"] * _matmul_params_per_layer(cfg) * plen
    attn = 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * (
        plen * (plen + 1) / 2.0
    )
    return base + attn + 2.0 * cfg["vocab"] * cfg["d_model"]


def weight_bytes(cfg: Dict) -> float:
    """Bytes of the served bf16 weights (norm parameters are f32)."""
    d, v, layers = cfg["d_model"], cfg["vocab"], cfg["n_layers"]
    mats = layers * _matmul_params_per_layer(cfg) + (1 if cfg["tie_embeddings"] else 2) * v * d
    norms = (2 * layers + 1) * d * (2 if cfg["norm"] == "layernorm" else 1)
    return BF16 * mats + 4 * norms


def kv_bytes_per_token(cfg: Dict) -> int:
    return cfg["n_layers"] * 2 * cfg["n_kv"] * cfg["head_dim"] * BF16


# --------------------------------------------------------------------------
# the reference
# --------------------------------------------------------------------------


def _fq(x, axis, quant):
    """Round ``x`` to float8 e4m3 with an absmax scale along ``axis``."""
    if quant is None:
        return x
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(a, b, quant):
    """a [..., K] @ b [K, N]."""
    return jnp.matmul(_fq(a, -1, quant), _fq(b, 0, quant), precision=HIGHEST)


def _norm(cfg, x, scale, bias):
    eps = cfg["norm_eps"]
    if cfg["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(cfg, x, pos):
    """x [T, H, hd]; rotate adjacent pairs of the first ``rope_frac`` of
    each head by angle ``pos * theta ** (-2i / rot)``."""
    hd = x.shape[-1]
    rot = int(hd * cfg["rope_frac"])
    rot -= rot % 2
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = pos[:, None].astype(F32) * inv  # [T, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([r.reshape(x.shape[:-1] + (rot,)), x[..., rot:]], -1)


def _attention(cfg, q, k, v, quant, q_block):
    """Causal grouped-query attention, one block of queries at a time.
    q [T, H, hd]; k, v [T, Hkv, hd]; T a multiple of ``q_block``."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    kt = _fq(jnp.repeat(k, g, axis=1), -1, quant).transpose(1, 2, 0)  # [H, hd, T]
    vq = _fq(jnp.repeat(v, g, axis=1), 0, quant).transpose(1, 0, 2)  # [H, T, hd]
    kpos = jnp.arange(t)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, 0)
        qb = _fq(qb, -1, quant).transpose(1, 0, 2)  # [H, qb, hd]
        sc = jnp.matmul(qb, kt, precision=HIGHEST) * hd ** -0.5  # [H, qb, T]
        qpos = start + jnp.arange(q_block)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = _fq(jax.nn.softmax(sc, axis=-1), -1, quant)
        return jnp.matmul(p, vq, precision=HIGHEST).transpose(1, 0, 2)  # [qb, H, hd]

    out = jax.lax.map(block, jnp.arange(0, t, q_block))
    return out.reshape(t, h, hd)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(cfgt, w, x, quant):
    cfg = dict(cfgt)
    t = x.shape[0]
    hq, hkv, hd = cfg["n_heads"], cfg["n_kv"], cfg["head_dim"]
    f32 = {k: v.astype(F32) for k, v in w.items()}
    pos = jnp.arange(t)
    bias = lambda n: f32.get((n, "bias"))
    h = _norm(cfg, x, f32[("norm_mixer", "scale")], bias("norm_mixer"))

    def proj(n):
        y = _mm(h, f32[("attn", n, "w")], quant)
        return y + f32[("attn", n, "b")] if ("attn", n, "b") in f32 else y

    q, k, v = proj("wq").reshape(t, hq, hd), proj("wk").reshape(t, hkv, hd), proj("wv").reshape(t, hkv, hd)
    q, k = _rope(cfg, q, pos), _rope(cfg, k, pos)
    q_block = 256 if t % 256 == 0 else t
    o = _attention(cfg, q, k, v, quant, q_block).reshape(t, hq * hd)
    attn = _mm(o, f32[("attn", "wo", "w")], quant)

    def mlp(u):
        gate = _mm(u, f32[("mlp", "w_gate")], quant)
        up = _mm(u, f32[("mlp", "w_up")], quant)
        return _mm(jax.nn.silu(gate) * up, f32[("mlp", "w_down")], quant)

    if cfg["parallel_block"]:
        return x + attn + mlp(h)
    x = x + attn
    return x + mlp(_norm(cfg, x, f32[("norm_ffn", "scale")], bias("norm_ffn")))


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(cfgt, top, x, rows, quant):
    cfg = dict(cfgt)
    x = x[rows]
    h = _norm(cfg, x, top[("final_norm", "scale")].astype(F32),
              top.get(("final_norm", "bias"), jnp.zeros((), F32)).astype(F32))
    table = top[("embed", "table")] if cfg["tie_embeddings"] else top[("lm_head",)]
    return _mm(h, table.astype(F32).T, quant)


def logits_at(
    cfg: Dict, seed: int, seqs: List[np.ndarray], score_from: List[int],
    quant: Optional[str] = None, pad_to: int = 1024,
) -> List[np.ndarray]:
    """Teacher-forced logits of each sequence at positions
    ``score_from[i] .. len(seqs[i]) - 1``, as float32 ``[n_i, vocab]``.

    Sequences run one at a time, right-padded to a multiple of ``pad_to``
    (causal attention keeps the padding out of every real position), all of
    them through one layer before the next layer's weights are made."""
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown reference precision {quant!r}")
    cfgt = tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (dict, list))))
    leaves = layout(cfg)
    top = W.top_weights(leaves, seed)
    table = top[("embed", "table")]
    xs = []
    for s in seqs:
        t = -(-len(s) // pad_to) * pad_to
        toks = np.zeros(t, np.int32)
        toks[: len(s)] = s
        xs.append(table[jnp.asarray(toks)].astype(F32))
    del table
    for layer in range(cfg["n_layers"]):
        w = W.layer_weights(leaves, seed, layer)
        xs = [_layer(cfgt, w, x, quant) for x in xs]
        del w
    out = []
    for x, s, f in zip(xs, seqs, score_from):
        n = len(s) - f
        rows = np.minimum(f + np.arange(-(-n // 64) * 64), len(s) - 1)
        out.append(np.asarray(_head(cfgt, top, x, jnp.asarray(rows), quant))[:n])
    return out
