import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script

1. builds ``input_specs`` (ShapeDtypeStructs only — no allocation),
2. builds the parameter/optimizer/cache shape trees with ``jax.eval_shape``,
3. assigns shardings from ``repro.distributed.sharding``,
4. ``jax.jit(step).lower(...).compile()`` against the production mesh,
5. records ``memory_analysis()`` (fit proof), ``cost_analysis()`` (FLOPs /
   bytes) and the collective traffic parsed from the compiled HLO — the
   inputs to EXPERIMENTS.md §Dry-run and §Roofline.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
        --mesh both --out experiments/dryrun

The 512 forced host devices exist ONLY in this process (the env var above is
set before any jax import, which locks the device count at first init).
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat, obs
from repro.configs import ARCHS, applicable_shapes, get_config, shape_by_name
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.hlo_census import census_hlo
from repro.core.roofline import device_peaks, model_flops, roofline_terms
from repro.distributed import (
    batch_shardings,
    cache_shardings,
    data_axes,
    param_shardings,
)
from repro.launch.mesh import make_production_mesh
from repro.models import api as model_api
from repro.optim import AdamWConfig, init_opt_state
from repro.train.loop import make_train_step

__all__ = ["input_specs", "run_cell", "main"]


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell
    (delegates to :func:`repro.models.api.input_specs`, the single owner of
    the per-family batch layout)."""
    return model_api.input_specs(
        cfg, batch=shape.global_batch, seq=shape.seq_len, kind=shape.kind
    )


def _param_specs(cfg: ArchConfig):
    import functools

    return jax.eval_shape(
        functools.partial(model_api.init_params, cfg), jax.random.key(0)
    )


def run_cell(
    cfg: ArchConfig,
    shape: ShapeConfig,
    mesh,
    *,
    mesh_name: str,
    keep_hlo: bool = False,
) -> Dict[str, Any]:
    """Lower + compile one cell; return the §Dry-run/§Roofline record."""
    t0 = time.time()
    n_chips = mesh.size
    params = _param_specs(cfg)
    p_sh = param_shardings(mesh, params)
    batch = input_specs(cfg, shape)
    b_sh = batch_shardings(mesh, cfg, batch)
    dp = data_axes(mesh)
    dp_axis = dp if len(dp) > 1 else dp[0]

    with compat.set_mesh(mesh):
        if shape.kind == "train":
            import functools

            opt_cfg = AdamWConfig(moment_dtype=cfg.moment_dtype)
            opt = jax.eval_shape(
                functools.partial(init_opt_state, cfg=opt_cfg), params
            )
            # moments inherit the 2-D param sharding (ZeRO via FSDP x TP)
            o_sh = jax.tree.map(lambda s: s, p_sh)
            opt_sh = type(opt)(
                step=NamedSharding(mesh, P()), mu=o_sh, nu=o_sh
            )
            raw_step = make_train_step(cfg, opt_cfg, jit=False)
            fn = jax.jit(
                raw_step,
                in_shardings=(p_sh, opt_sh, b_sh),
                donate_argnums=(0, 1),
            )
            lowered = fn.lower(params, opt, batch)
        elif shape.kind == "prefill":
            import functools

            def prefill_fn(params, batch):
                return model_api.prefill(
                    cfg, params, batch, shape.seq_len, jnp.bfloat16
                )

            out_caches = jax.eval_shape(prefill_fn, params, batch)[1]
            c_out_sh = cache_shardings(mesh, cfg, out_caches, layout="prefill")
            fn = jax.jit(
                prefill_fn,
                in_shardings=(p_sh, b_sh),
                out_shardings=(NamedSharding(mesh, P()), c_out_sh),
            )
            lowered = fn.lower(params, batch)
        else:  # decode
            import functools

            caches = jax.eval_shape(
                functools.partial(
                    model_api.init_state,
                    cfg,
                    shape.global_batch,
                    shape.seq_len,
                    jnp.bfloat16,
                )
            )
            c_sh = cache_shardings(mesh, cfg, caches)
            tok_sh = NamedSharding(
                mesh,
                P(dp_axis if shape.global_batch % _axis(mesh, dp_axis) == 0 else None, None),
            )

            def decode_fn(params, token, caches, pos):
                return model_api.decode(cfg, params, token, caches, pos)

            fn = jax.jit(
                decode_fn,
                in_shardings=(p_sh, tok_sh, c_sh, NamedSharding(mesh, P())),
                out_shardings=(NamedSharding(mesh, P()), c_sh),
                donate_argnums=(2,),
            )
            lowered = fn.lower(
                params,
                batch["token"],
                caches,
                jax.ShapeDtypeStruct((), jnp.int32),
            )

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compat.normalize_memory_analysis(compiled)
    ca = compat.normalize_cost_analysis(compiled)
    hlo = compiled.as_text()
    # Loop-aware census: cost_analysis counts while bodies once (useless for
    # scanned layers); the census multiplies by known_trip_count. See
    # repro.core.hlo_census.
    census = census_hlo(hlo)

    flops_dev = census.flops
    bytes_dev = census.hbm_bytes
    mf = model_flops(
        model_api.param_count(cfg),
        shape.tokens_per_step,
        kind="train" if shape.kind == "train" else "infer",
        n_params_active=model_api.active_param_count(cfg),
    )
    rt = roofline_terms(
        flops_dev,
        bytes_dev,
        census.collective_bytes,
        hw=device_peaks(),
        model_flops_total=mf,
        n_chips=n_chips,
    )

    rec = {
        "arch": cfg.name,
        "shape": shape.name,
        "mesh": mesh_name,
        "n_chips": n_chips,
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            # peak_bytes is the buffer-assignment high-water mark including
            # arguments, (aliased) outputs and live temps — the per-chip HBM
            # requirement (upper-bounded from components on JAX without
            # peak_memory_in_bytes). temp_bytes sums logical temp buffers
            # (reused buffers counted once each, not concurrent) — diagnostic
            # only.
            **ma,
            "hbm_need_bytes": ma["peak_bytes"],
            "fits_16gb": ma["peak_bytes"] < 16e9,
        },
        "cost": {
            "flops_per_device": flops_dev,
            "bytes_per_device": bytes_dev,
            "xla_cost_analysis_flops": float(ca.get("flops", 0.0)),
            "census": census.summary(),
        },
        "collectives": census.collective_by_kind,
        "model_flops_total": mf,
        "roofline": rt.summary(),
    }
    return rec


def _apply_overrides(cfg: ArchConfig, overrides):
    """Apply ``field=value`` (or ``moe.field=value``) config overrides."""
    for ov in overrides:
        key, _, raw = ov.partition("=")
        value: Any
        if raw.lower() in ("true", "false"):
            value = raw.lower() == "true"
        else:
            try:
                value = int(raw)
            except ValueError:
                try:
                    value = float(raw)
                except ValueError:
                    value = raw
        if key.startswith("moe."):
            if cfg.moe is None:
                continue
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, **{key[4:]: value})
            )
        else:
            cfg = dataclasses.replace(cfg, **{key: value})
    return cfg


def _axis(mesh, axis):
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument(
        "--override", action="append", default=[],
        help="ArchConfig field override, e.g. attn_seq_shard=true, "
        "remat_policy=dots, moe.dispatch=sort, scan_chunk=16 (§Perf knobs)",
    )
    args = ap.parse_args()

    log = obs.get_logger("dryrun")
    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    meshes = (
        ["single", "multi"] if args.mesh == "both" else [args.mesh]
    )
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        for arch in archs:
            cfg = get_config(arch)
            cfg = _apply_overrides(cfg, args.override)
            shapes = (
                applicable_shapes(cfg)
                if args.shape == "all"
                else [shape_by_name(s) for s in args.shape.split(",")]
            )
            for shape in shapes:
                if shape.name == "long_500k" and not cfg.supports_long:
                    log.info("skip", arch=arch, shape=shape.name,
                             reason="full-attn")
                    continue
                out_path = os.path.join(
                    args.out, mesh_name, f"{arch}__{shape.name}.json"
                )
                os.makedirs(os.path.dirname(out_path), exist_ok=True)
                if args.skip_existing and os.path.exists(out_path):
                    log.info("cached", arch=arch, shape=shape.name,
                             mesh=mesh_name)
                    continue
                try:
                    rec = run_cell(
                        cfg, shape, mesh, mesh_name=mesh_name,
                        keep_hlo=args.keep_hlo,
                    )
                    r = rec["roofline"]
                    log.info(
                        "ok", arch=arch, shape=shape.name, mesh=mesh_name,
                        compile_s=rec["compile_s"],
                        hbm_gb=rec["memory"]["hbm_need_bytes"] / 1e9,
                        dominant=r["dominant"],
                        roofline_frac=r["roofline_fraction"],
                    )
                except Exception as e:
                    failures += 1
                    rec = {
                        "arch": arch,
                        "shape": shape.name,
                        "mesh": mesh_name,
                        "status": "fail",
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-3000:],
                    }
                    log.info("fail", arch=arch, shape=shape.name,
                             mesh=mesh_name, error=str(e))
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
    log.info("done", failures=failures)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
