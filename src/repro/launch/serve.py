"""Serving driver: ``python -m repro.launch.serve --arch <id> [...]``.

Two engines over the same compiled prefill/decode substrate:

* ``--engine continuous`` (default) — the continuous-batching subsystem:
  FIFO bucketed scheduler, slot-pooled KV cache, one fused masked decode
  step; requests from a Poisson-ish arrival trace join and leave mid-flight.
* ``--engine static`` — the lockstep ``ServeEngine`` baseline: one batch
  enters and exits together.

Reduced configs run real tokens on CPU; on a TPU the published configs
serve at full width (``chip_smoke.py`` at the repo root drives this path).
JAX's persistent compilation cache is placed by
:mod:`repro.launch.compile_cache`.
"""

from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serve import (
    ContinuousEngine,
    ServeEngine,
    gen_len_spread,
    poisson_trace,
)


def _static(cfg, params, args) -> None:
    key = jax.random.key(args.seed)
    batch = {
        "tokens": jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab
        )
    }
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            key, (args.batch, cfg.n_img_tokens, cfg.d_model), jnp.float32
        )
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.enc_seq, cfg.d_model), jnp.float32
        )
    eng = ServeEngine(
        cfg=cfg,
        params=params,
        max_len=args.prompt_len + args.gen,
        cache_dtype=jnp.float32 if cfg.param_dtype == "float32" else jnp.bfloat16,
        temperature=args.temperature,
    )
    log = obs.get_logger("serve")
    t0 = time.perf_counter()
    toks = eng.generate(batch, args.gen, key=key)
    dt = time.perf_counter() - t0
    log.info(
        "generated", shape=str(tuple(toks.shape)), wall_s=dt,
        tokens_per_sec=args.batch * args.gen / dt,
    )
    log.info("first_sequence", tokens=str(toks[0].tolist()))


def _continuous(cfg, params, args):
    gens = (args.gen,) if args.fixed_gen else gen_len_spread(args.gen)
    trace = poisson_trace(
        args.n_requests, seed=args.seed, vocab=cfg.vocab,
        prompt_lens=(args.prompt_len // 4 or 1, args.prompt_len // 2 or 1,
                     args.prompt_len),
        gen_lens=gens, mean_interarrival=args.rate,
    )
    eng = ContinuousEngine(
        cfg=cfg,
        params=params,
        n_slots=args.slots,
        max_len=args.prompt_len + args.gen,
        cache_dtype=jnp.float32 if cfg.param_dtype == "float32" else jnp.bfloat16,
        temperature=args.temperature,
        kv_format=args.kv_format,
    )
    log = obs.get_logger("serve")
    report = eng.timed_serve(trace, key=jax.random.key(args.seed))
    log.info(
        "served", requests=len(trace), tokens=report.generated_tokens,
        wall_s=report.wall_time_s, tokens_per_sec=report.tokens_per_sec,
    )
    log.info(
        "counters", decode_steps=report.decode_steps,
        prefill_batches=report.prefill_batches,
        mean_occupancy=report.mean_occupancy,
    )
    log.info(
        "latency", ttft_p50_s=report.ttft_p50, ttft_p99_s=report.ttft_p99,
        itl_p50_s=report.itl_p50, itl_p99_s=report.itl_p99,
    )
    log.info(
        "phases", queue_p50_s=report.queue_p50, queue_p99_s=report.queue_p99,
        attach_p50_s=report.attach_p50,
        chunk_prefill_p50_s=report.chunk_prefill_p50,
        slot_hwm=report.slot_hwm,
    )
    if report.goodput is not None:
        log.info("goodput", fraction=report.goodput)
    if report.kv_bytes_per_slot:
        log.info(
            "kv_cache", format=args.kv_format or "full-width",
            kb_per_slot=report.kv_bytes_per_slot / 1e3,
        )
    first = trace[0]
    log.info(
        "first_request", uid=first.uid, prompt_tokens=len(first.prompt),
        output=str(report.outputs[first.rid]),
    )
    return eng, trace, report


def main(argv: Optional[List[str]] = None):
    """Run the serving CLI; the continuous engine returns
    ``(engine, trace, report)`` (None for the static engine)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--engine", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--batch", type=int, default=4,
                    help="static engine: lockstep batch size")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous engine: decode slot pool size")
    ap.add_argument("--n-requests", type=int, default=12,
                    help="continuous engine: trace length")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="continuous engine: mean interarrival (decode steps)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--fixed-gen", action="store_true",
                    help="continuous engine: every request's budget is "
                    "exactly --gen (default: a spread up to --gen)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-format", default=None,
                    choices=(None, "int8", "fp8_e4m3", "fp8_e5m2"),
                    help="continuous engine: narrow K/V lanes (~4x less "
                    "cache memory per slot)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--linger-seconds", type=float, default=0.0,
                    help="keep the process (and its REPRO_METRICS_PORT "
                    "scrape server) alive this long after the run, so "
                    "/metrics, /requests and /trace can be curled against "
                    "the frozen registry (Ctrl-C/SIGINT ends the linger "
                    "early but still runs the atexit dump hooks)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    log = obs.get_logger("serve")
    server = obs.http.maybe_serve_from_env()
    if server is not None:
        log.info(
            "metrics_server", port=server.port,
            endpoints="/metrics /requests /trace",
        )

    params = api.init_params(cfg, jax.random.key(args.seed))
    result = None
    if args.engine == "static" or cfg.family in ("audio", "vlm"):
        if args.engine == "continuous":
            log.info("engine_fallback", family=cfg.family, engine="static")
        _static(cfg, params, args)
    else:
        result = _continuous(cfg, params, args)

    if args.linger_seconds > 0 and server is not None:
        # The run is done and nothing mutates the registry anymore: what
        # /metrics serves now is byte-identical to what REPRO_METRICS_DUMP
        # will write at exit — the property the CI scrape smoke asserts.
        log.info(
            "metrics_linger", port=server.port, seconds=args.linger_seconds
        )
        try:
            time.sleep(args.linger_seconds)
        except KeyboardInterrupt:
            pass
    return result


if __name__ == "__main__":
    main()
