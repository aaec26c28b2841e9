#!/usr/bin/env python3
"""Bring-up smoke run on one TPU: chatglm3-6b served at full width through
the compiled O-POPE kernels.

    python3 chip_smoke.py [--seed N]

Everything runs in this one process (a chip belongs to one process).

* Phase 0, device: print the JAX version and devices. Anything but a TPU
  exits with code 2 before any work: this script never runs on the CPU.
* Phase 1, kernels: ``ops.matmul(..., backend="pallas")`` in bf16 at the
  chatglm3-6b decode (M=4) and prefill (M=2048) shapes — QKV, the MLP
  gate GEMM with its ``silu``+``mul`` epilogue, the MLP down and attention
  output GEMMs with ``residual`` — each checked against ``kernels/ref.py``
  in fp32; one ``grouped_matmul`` and one ``pallas_q8`` call; ``auto``
  must resolve to ``pallas``.
* Phase 2, serve: random chatglm3-6b parameters from ``--seed`` (28
  layers, d_model 4096, vocab 65024, bf16), 8 requests through
  ``repro.launch.serve``'s continuous engine (4 slots, prompts of
  128-512 tokens, 32 greedy tokens each), served twice: the first run
  compiles, the second is steady. Checks: every request gets its full
  budget, one decode program, no backend degradation, every GEMM on
  ``pallas``; then one prefill bucket and one decode step agree between
  the default ``pallas`` path and ``backend="xla"`` (``lax.dot_general``),
  with finite logits.

Any failed check raises and the script exits non-zero. The last line of
standard output is ``{"ok": true, "device": {...}}`` and is printed only
when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "chatglm3-6b"
DECODE_M = 4  # one decode step over 4 slots
PREFILL_M = 2048  # a 4 x 512 prompt bucket
KERNEL_TOL = 1e-2  # bf16 output rounding is 2**-8 of each element
Q8_TOL = 0.05  # the q8 family's audit policy (quant/backends.py)
# Logit agreement, pallas vs xla, as max|diff| / max|xla logit|. Both paths
# accumulate in fp32 and round each GEMM output to bf16 once; they differ
# in accumulation order (the kernel sums K in block_k panels), and a flipped
# bf16 rounding compounds through 28 layers. The same comparison on the CPU
# (Pallas interpreter vs xla, 28 layers at d_model 1024) gave 1.2e-2 for
# prefill and 1.3e-2 for decode; a wrong kernel gives errors of order 1.
LOGIT_TOL = 1e-1


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# --------------------------------------------------------------------------
# Phase 0: device
# --------------------------------------------------------------------------


def phase_device():
    import jax

    log(f"[phase 0] jax {jax.__version__}; devices: {jax.devices()}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, found platform {dev.platform!r}; "
            "not continuing on it",
            file=sys.stderr,
        )
        sys.exit(2)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}", file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    return dev


# --------------------------------------------------------------------------
# Phase 1: kernels
# --------------------------------------------------------------------------


def _rel_err(got, ref) -> float:
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def phase_kernels(cfg, *, decode_m: int = DECODE_M, prefill_m: int = PREFILL_M):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.ref import reference_grouped_matmul, reference_matmul

    f32, bf16 = jnp.float32, jnp.bfloat16
    resolved = ops.resolve_backend("auto")
    log(f"[phase 1] resolve_backend('auto') = {resolved!r}")
    check(resolved == "pallas", f"auto resolved to {resolved!r}, not 'pallas'")

    d, ff = cfg.d_model, cfg.d_ff
    qkv_n = (cfg.n_heads + 2 * cfg.n_kv) * cfg.head_dim_
    key = jax.random.key(0)

    def normal(shape, scale=1.0):
        nonlocal key
        key, sub = jax.random.split(key)
        return (jax.random.normal(sub, shape, f32) * scale).astype(bf16)

    def ref(a, b):
        return reference_matmul(a.astype(f32), b.astype(f32))

    t0 = time.perf_counter()
    for phase, m in (("decode", decode_m), ("prefill", prefill_m)):
        x, x_ff = normal((m, d)), normal((m, ff))
        res = normal((m, d))
        w_qkv = normal((d, qkv_n), d ** -0.5)
        w_gate, w_up = normal((d, ff), d ** -0.5), normal((d, ff), d ** -0.5)
        w_down, w_o = normal((ff, d), ff ** -0.5), normal((d, d), d ** -0.5)
        up = ops.matmul(x, w_up, backend="pallas")
        cases = (
            ("qkv", x, w_qkv, None,
             lambda: ref(x, w_qkv)),
            ("mlp_up", x, w_up, None,
             lambda: ref(x, w_up)),
            ("mlp_gate+silu*mul", x, w_gate, ["silu", ("mul", up)],
             lambda: jax.nn.silu(ref(x, w_gate)) * up.astype(f32)),
            ("mlp_down+residual", x_ff, w_down, [("residual", res)],
             lambda: ref(x_ff, w_down) + res.astype(f32)),
            ("attn_out+residual", x, w_o, [("residual", res)],
             lambda: ref(x, w_o) + res.astype(f32)),
        )
        for name, a, b, ep, want in cases:
            got = ops.matmul(a, b, backend="pallas", epilogue=ep)
            check(got.dtype == bf16, f"{name}: output dtype {got.dtype}")
            err = _rel_err(got, want())
            log(
                f"[phase 1] {phase} {name} M={a.shape[0]} K={a.shape[1]} "
                f"N={b.shape[1]}: max_rel_err={err:.3e} (tol {KERNEL_TOL})"
            )
            check(err <= KERNEL_TOL, f"{phase} {name}: error {err} > {KERNEL_TOL}")

    g_a, g_b = normal((4, 256, d)), normal((4, d, 1024), d ** -0.5)
    got = ops.grouped_matmul(g_a, g_b, backend="pallas")
    err = _rel_err(got, reference_grouped_matmul(g_a.astype(f32), g_b.astype(f32)))
    log(f"[phase 1] grouped G=4 M=256 K={d} N=1024: max_rel_err={err:.3e}")
    check(err <= KERNEL_TOL, f"grouped: error {err} > {KERNEL_TOL}")

    q_a, q_b = normal((decode_m, d)), normal((d, qkv_n), d ** -0.5)
    check(ops.resolve_backend("pallas_q8") == "pallas_q8", "pallas_q8 not resolved")
    got = ops.matmul(q_a, q_b, backend="pallas_q8", out_dtype=f32)
    err = _rel_err(got, ref(q_a, q_b))
    log(f"[phase 1] pallas_q8 M={decode_m} K={d} N={qkv_n}: max_rel_err={err:.3e} "
        f"(tol {Q8_TOL})")
    check(err <= Q8_TOL, f"pallas_q8: error {err} > {Q8_TOL}")
    log(f"[phase 1] ok in {time.perf_counter() - t0:.2f}s (compiles included)")


# --------------------------------------------------------------------------
# Phase 2: serve
# --------------------------------------------------------------------------


def _counters(name: str):
    from repro import obs

    return dict(obs.snapshot()["counters"].get(name, {}))


def _labels(key: str):
    return dict(part.split("=", 1) for part in key.split(",") if part)


def phase_serve(seed: int, *, arch: str = ARCH, prompt_len: int = 512):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve
    from repro.models import api

    compiles = []  # (host clock at the end of a backend compile, seconds)

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append((time.perf_counter(), secs))

    def compile_s(start, end):
        return sum(s for t, s in compiles if start <= t < end)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    calls_before = _counters("gemm.calls")
    argv = [
        "--arch", arch, "--engine", "continuous", "--n-requests", "8",
        "--slots", "4", "--prompt-len", str(prompt_len), "--gen", "32",
        "--fixed-gen", "--temperature", "0", "--seed", str(seed),
    ]
    log(f"[phase 2] serve argv: {' '.join(argv)}")
    t0 = time.perf_counter()
    eng, trace, first = serve.main(argv)
    t1 = time.perf_counter()
    cfg = eng.cfg
    n_params = sum(int(x.size) for x in jax.tree.leaves(eng.params))
    log(
        f"[phase 2] model {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, vocab {cfg.vocab}, {cfg.param_dtype}, "
        f"{n_params} parameters"
    )
    t_serve = t1 - first.wall_time_s  # set-up ends where the first serve starts
    steady = eng.timed_serve(trace, key=jax.random.key(seed))
    t2 = time.perf_counter()
    log(
        f"[phase 2] setup_s={t_serve - t0:.3f} (parameter init + engine "
        f"build; backend compile {compile_s(t0, t_serve):.3f}s of it)"
    )
    log(
        f"[phase 2] first_serve_s={first.wall_time_s:.3f} (backend compile "
        f"{compile_s(t_serve, t1):.3f}s of it)"
    )
    log(
        f"[phase 2] steady_serve_s={steady.wall_time_s:.3f} "
        f"({steady.generated_tokens} tokens, {steady.decode_steps} decode "
        f"steps, {steady.prefill_batches} prefill batches, backend compile "
        f"{compile_s(t1, t2):.3f}s)"
    )
    n_steady = sum(1 for t, _ in compiles if t1 <= t < t2)
    check(n_steady == 0, f"{n_steady} compiles during the steady serve")

    for run, report in (("first", first), ("steady", steady)):
        for r in trace:
            toks = report.outputs[r.rid]
            check(
                len(toks) == r.max_new_tokens == 32,
                f"{run}: request {r.rid} got {len(toks)} of "
                f"{r.max_new_tokens} tokens",
            )
            check(
                all(0 <= t < cfg.vocab for t in toks),
                f"{run}: request {r.rid} has out-of-vocab tokens",
            )
    check(
        first.outputs == steady.outputs,
        "greedy outputs differ between the first and the steady run",
    )
    n_dec = eng.decode_compilations()
    log(f"[phase 2] decode_compilations={n_dec}; prompt lengths "
        f"{sorted({len(r.prompt) for r in trace})}")
    check(n_dec == 1, f"decode compiled {n_dec} times")

    calls = _counters("gemm.calls")
    served = {
        _labels(k)["backend"]
        for k, v in calls.items() if v > calls_before.get(k, 0.0)
    }
    log(f"[phase 2] gemm.calls backends while serving: {sorted(served)}")
    check(served == {"pallas"}, f"serving GEMMs ran on {sorted(served)}")
    degr = _counters("gemm.degradations")
    check(not degr, f"backend degradations: {degr}")

    # One prefill bucket (4 rows, bucket 512, ragged lengths) and one decode
    # step at each row's next position, pallas (default) vs xla.
    rows, lb = 4, prompt_len
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (rows, lb)), jnp.int32)
    lengths = jnp.asarray([lb - 1, (3 * lb) // 4, lb // 2, lb // 5], jnp.int32)
    prefill = jax.jit(
        lambda p, t, n, be: api.prefill_bucketed(cfg, p, t, n, backend=be),
        static_argnums=3,
    )
    decode = jax.jit(
        lambda p, t, c, pos, be: api.decode_at(cfg, p, t, c, pos, backend=be),
        static_argnums=4,
    )
    out = {}
    nxt = None
    for be in (None, "xla"):
        pf_logits, caches = prefill(eng.params, tokens, lengths, be)
        if nxt is None:
            nxt = jnp.argmax(pf_logits, axis=-1).astype(jnp.int32)[:, None]
        dec_logits, _ = decode(eng.params, nxt, caches, lengths, be)
        out[be or "pallas"] = (pf_logits, dec_logits)
    for i, step in enumerate(("prefill", "decode")):
        lp, lx = (out[b][i].astype(jnp.float32) for b in ("pallas", "xla"))
        check(bool(jnp.isfinite(lp).all()), f"{step}: pallas logits not finite")
        check(bool(jnp.isfinite(lx).all()), f"{step}: xla logits not finite")
        err = float(jnp.max(jnp.abs(lp - lx)) / jnp.max(jnp.abs(lx)))
        agree = float(jnp.mean(jnp.argmax(lp, -1) == jnp.argmax(lx, -1)))
        log(
            f"[phase 2] {step} logits pallas vs xla, bucket {rows}x{lb}, "
            f"lengths {[int(x) for x in lengths]}: max|diff|/max|xla|="
            f"{err:.3e} (tol {LOGIT_TOL}), argmax agreement {agree:.2f}"
        )
        check(err <= LOGIT_TOL, f"{step}: logits differ by {err} > {LOGIT_TOL}")
    check(not _counters("gemm.degradations"), "backend degradations")
    log("[phase 2] ok")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random parameters and prompts")
    args = ap.parse_args()

    dev = phase_device()
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[phase 0] device {dev.device_kind!r}; compile cache "
        f"{enable_compile_cache()}")
    phase_kernels(get_config(ARCH))
    phase_serve(args.seed)

    import jax

    devs = jax.devices()
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
