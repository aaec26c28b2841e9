"""Fault-tolerant training loop.

Responsibilities:

* **step function factory** — builds the jitted train step for an arch config
  (loss -> grad -> AdamW), with gradient accumulation (``cfg.grad_accum``
  microbatches via ``lax.scan``; grok-1 needs 8x to fit activations) and
  optional donation of params/opt state.
* **checkpoint/restart** — auto-resumes from the newest complete checkpoint;
  `AsyncCheckpointer` writes every ``ckpt_every`` steps off-thread. Because
  the data pipeline is step-indexed and deterministic, a restart replays the
  exact token stream (verified in tests by killing mid-run).
* **straggler watchdog** — flags steps slower than ``watchdog_factor`` x the
  running median (on a real fleet this triggers hot-spare swap; here it logs
  and counts, and tests inject a synthetic stall).
* **elastic re-scale** — a checkpoint written on one mesh restores onto
  another (host-side full arrays; see checkpoint.restore).
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro import obs as _obs
from repro.configs.base import ArchConfig
from repro.core import roofline as _roofline
from repro.models import api as model_api
from repro.optim.adamw import AdamWConfig, OptState, apply_updates, init_opt_state
from repro.checkpoint import checkpoint as ckpt

__all__ = ["TrainLoopConfig", "make_train_step", "train", "TrainResult"]


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    log_every: int = 10
    watchdog_factor: float = 3.0
    fail_at_step: Optional[int] = None  # fault-injection hook (tests)


@dataclasses.dataclass
class TrainResult:
    losses: list
    resumed_from: Optional[int]
    straggler_steps: int
    final_step: int


def make_train_step(
    cfg: ArchConfig,
    opt_cfg: AdamWConfig,
    *,
    donate: bool = True,
    jit: bool = True,
    policy=None,
) -> Callable:
    """Returns jit'd ``(params, opt_state, batch) -> (params, opt_state, metrics)``.

    With ``cfg.grad_accum > 1`` the global batch's leading dim is split into
    microbatches scanned sequentially, accumulating fp32 grads — the
    activation-memory lever that fits grok-1's 1M-token steps.

    ``policy`` (a backend name or :class:`repro.quant.PrecisionPolicy`)
    selects per-role forward matmul precision. The fp32 master path is
    untouched by any policy: gradients route through each backend's
    registered full-precision grad backend, accumulation stays fp32, and the
    optimizer moments/updates never see a quantized value.
    """

    def loss(params, batch):
        return model_api.loss_fn(cfg, params, batch, backend=policy)

    def step(params, opt_state, batch):
        n_micro = cfg.grad_accum
        if n_micro <= 1:
            l, grads = jax.value_and_grad(loss)(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
                batch,
            )

            def accum(carry, mb):
                tot, g = carry
                li, gi = jax.value_and_grad(loss)(params, mb)
                g = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g, gi
                )
                return (tot + li, g), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (tot, grads), _ = jax.lax.scan(accum, (jnp.zeros(()), g0), micro)
            l = tot / n_micro
            grads = jax.tree.map(lambda g: g / n_micro, grads)
        params, opt_state, metrics = apply_updates(params, grads, opt_state, opt_cfg)
        metrics["loss"] = l
        return params, opt_state, metrics

    if not jit:
        return step
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


class _Watchdog:
    def __init__(self, factor: float):
        self.factor = factor
        self.times: list = []
        self.flagged = 0

    def observe(self, dt: float) -> bool:
        slow = (
            len(self.times) >= 5
            and dt > self.factor * statistics.median(self.times)
        )
        self.times.append(dt)
        if len(self.times) > 50:
            self.times.pop(0)
        if slow:
            self.flagged += 1
        return slow


def train(
    cfg: ArchConfig,
    opt_cfg: AdamWConfig,
    loop: TrainLoopConfig,
    batch_fn: Callable[[int], Dict[str, jax.Array]],
    *,
    init_key: Optional[jax.Array] = None,
    params: Any = None,
    log: Callable[[str], None] = print,
    policy=None,
) -> TrainResult:
    """Run (or resume) training. ``batch_fn(step)`` must be deterministic."""
    if params is None:
        if init_key is None:
            init_key = jax.random.key(0)
        params = model_api.init_params(cfg, init_key)
    opt_state = init_opt_state(
        params, dataclasses.replace(opt_cfg, moment_dtype=cfg.moment_dtype)
    )
    opt_cfg = dataclasses.replace(opt_cfg, moment_dtype=cfg.moment_dtype)

    start = 0
    resumed_from = None
    writer = None
    if loop.ckpt_dir:
        writer = ckpt.AsyncCheckpointer(loop.ckpt_dir)
        last = ckpt.latest_step(loop.ckpt_dir)
        if last is not None:
            state = ckpt.restore(
                loop.ckpt_dir, last, like={"params": params, "opt": opt_state}
            )
            params, opt_state = state["params"], state["opt"]
            start = last
            resumed_from = last
            log(f"[train] resumed from step {last}")

    step_fn = make_train_step(cfg, opt_cfg, policy=policy)
    wd = _Watchdog(loop.watchdog_factor)
    losses = []
    # Per-step telemetry baseline: parameter count for the 6*N*D train-FLOP
    # estimate (core.roofline.model_flops), so each step event carries
    # achieved GFLOP/s and its fraction of the reference roofline.
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    peak_flops = _roofline.device_peaks().peak_flops
    try:
        for step in range(start, loop.total_steps):
            if loop.fail_at_step is not None and step == loop.fail_at_step:
                raise RuntimeError(f"injected failure at step {step}")
            t0 = time.perf_counter()
            batch = batch_fn(step)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.perf_counter() - t0
            if _obs.enabled():
                tok_arr = batch.get("tokens", next(iter(batch.values())))
                tokens = int(tok_arr.size)
                flops = _roofline.model_flops(n_params, tokens, kind="train")
                tok_s = tokens / dt if dt else 0.0
                gflops = flops / dt / 1e9 if dt else 0.0
                _obs.histogram("train.step_seconds").observe(dt)
                _obs.gauge("train.tokens_per_sec").set(tok_s)
                _obs.event(
                    "train_step",
                    step=step,
                    loss=loss,
                    wall_s=dt,
                    tokens=tokens,
                    tokens_per_sec=tok_s,
                    gflops_per_sec=gflops,
                    roofline_frac=flops / dt / peak_flops if dt else 0.0,
                )
            if wd.observe(dt):
                log(f"[train] straggler: step {step} took {dt:.3f}s")
                _obs.counter("train.stragglers").inc()
                _obs.event("straggler", step=step, wall_s=dt)
            if loop.log_every and step % loop.log_every == 0:
                log(
                    f"[train] step {step} loss {loss:.4f} "
                    f"lr {float(metrics['lr']):.2e} gnorm {float(metrics['grad_norm']):.3f} "
                    f"{dt*1e3:.0f}ms"
                )
            if writer and (step + 1) % loop.ckpt_every == 0:
                writer.save(step + 1, {"params": params, "opt": opt_state})
        if writer:
            writer.save(loop.total_steps, {"params": params, "opt": opt_state})
            writer.wait()
    finally:
        # A failing step must not also lose the checkpoint already in flight:
        # join the async writer so every save issued before the failure is
        # committed (the graceful-shutdown analogue of a SIGTERM flush; a hard
        # kill still loses at most one interval, as documented in checkpoint).
        if writer:
            try:
                writer.wait()
            except Exception as flush_err:  # don't mask the original failure
                log(f"[train] checkpoint flush failed: {flush_err}")
    return TrainResult(
        losses=losses,
        resumed_from=resumed_from,
        straggler_steps=wd.flagged,
        final_step=loop.total_steps,
    )
