"""Public jit'd matmul entry point used by every layer in the framework.

``matmul`` routes through a **backend registry** with identical numerics
across backends (fp32 accumulation, single final cast — see `ref.py`):

* ``"pallas"``            — the O-POPE Pallas kernel, compiled (TPU).
* ``"pallas_interpret"``  — same kernel body, Pallas interpreter (CPU tests).
* ``"xla"``               — ``jax.lax.dot_general`` with
  ``preferred_element_type=f32``; used for the CPU dry-run, where Pallas
  cannot lower, and as the A/B comparison baseline in benchmarks.

New backends register with :func:`register_backend` (an availability probe
gates selection). The default ``"auto"`` resolver takes ``pallas`` on a TPU
and ``xla`` elsewhere, so model code is backend-agnostic. On a TPU the
compiled path is probed once, lazily: a probe the compiler refuses
**raises** with the compiler's message — nothing on a TPU quietly runs as a
plain XLA dot or in the Pallas interpreter. Off the TPU, an explicitly
requested backend that is unavailable degrades along its *fallback chain*
(default ``pallas_interpret`` then ``xla``; a registered backend may declare
its own chain — the quantized backends fall back to ``xla_q8`` so
degradation preserves quantized numerics) rather than raising. On a TPU the
``*_interpret`` backends are never a degradation target; they run only when
named.

Quantized backends (``xla_q8``, ``pallas_q8`` — see :mod:`repro.quant`)
register themselves on first use: an unknown backend name triggers one lazy
``import repro.quant`` before resolution fails, so callers never import the
quant package explicitly just to name its backends.

The pallas backends pick block shapes through one memoized resolution path,
``_tile_for``, keyed per ``(backend, shape-family, M, K, N, G, dtype)`` so a
grouped GEMM can never collide with a dense one of the same (M, K, N). The
resolution order is **tuned table first, heuristic second**: a persistent
tuning table written by :mod:`repro.tune` (the ``repro-tune`` CLI; location
overridable via ``REPRO_TUNE_TABLE``) is consulted for an empirically
measured winner on this device kind, and only on a miss does the backend's
registered ``tile_fn`` heuristic (``opope_gemm.default_block_shape`` — the
VMEM-budget analogue of the paper's tile quantization rule — or the q8
variant) decide. Tuned tiles are validated against the kernel's hard
constraints (alignment, VMEM budget) before use; :func:`tile_source` reports
which path won for a given shape. The memo is LRU-bounded
(``_TILE_CACHE_CAP``): a long-lived serving process that sees an unbounded
stream of request shapes must not grow it without limit.
:func:`clear_tile_cache` drops both the memo and the loaded table state.

A ``custom_vjp`` makes the backward pass run the same O-POPE dataflow (two
more GEMMs: dA = dO @ B^T, dB = A^T @ dO) instead of whatever XLA would pick
for the transposed dots. A backend registered with ``grad_backend=`` runs
its backward GEMMs on that backend instead — how the quantized paths encode
the paper's "accuracy-sensitive tasks such as training still require
higher-precision floating-point formats": forward may be q8, gradients are
always full-precision fp32-accumulated.

Each backend is a **family**: alongside the 2-D ``fn`` it may register a
``grouped`` member (``[G, M, K] @ [G, K, N]`` — :func:`grouped_matmul`), so
batched shape families (MoE expert FFNs) route through the same names,
resolver, fallback chains and grad-backend rule as single GEMMs. Backends
also declare a numerics ``family`` tag (``"fp"``/``"q8"``): a fallback chain
may change the execution engine but must land on a terminal of the same
family — degradation never silently changes quantization behaviour
(asserted registry-wide by ``tests/test_backend_conformance.py`` and the CI
introspection step).

**Fused epilogues** (:mod:`repro.kernels.epilogue`): ``matmul`` and
``grouped_matmul`` take an ``epilogue=`` pipeline of registered post-ops
(activations, bias, residual, RMSNorm scale, re-quantize) applied to the
fp32 accumulator before the single final cast. Backends registered with
``epilogue_fused=True`` run the pipeline *inside* their kernel at the
accumulator writeback (the O-POPE point: the result is touched once); every
other backend — including any fallback a request degrades onto — gets the
**post-hoc lane**: the backend produces the fp32 accumulator, the same op
pipeline runs on it under ``jax.named_scope("opope_epilogue")``, then the
one cast. The two lanes are numerically identical by construction, so the
conformance contract extends to epilogues unchanged, and degradation can
never drop or double-apply a requested epilogue. Whether a *capable*
backend actually fuses is a per-shape decision: tuning-table verdict first
(:mod:`repro.tune` measures fused vs post-hoc), fuse-by-default second —
:func:`fusion_source` reports which. The custom_vjp rules recompute the
pre-epilogue accumulator in the backward pass (one extra GEMM — the fused
forward never materializes it), backprop through the op pipeline, then run
the usual two transposed GEMMs on the grad backend.

**Stacked weights read in place** (serving only): ``matmul``/``linear`` take
``b`` as a :class:`LayerWeight` — one layer of a stacked ``[L, K, N]``
parameter plus its layer index — so a layer scan need not copy each weight
out of the stack before the GEMM. Backends registered with
``reads_stacked=True`` read the layer in place (``pallas``: the kernel's B
index map selects the layer; ``xla``: a dynamic slice XLA fuses into the
dot); every other backend (the q8 family, grouped members) is handed the
slice, exactly what a scan over the stack would hand it. Like the
pre-quantized A lane this has no custom_vjp; training scans the stack as
before. ``gemm.calls`` labels each call ``b=stacked`` or ``b=array``.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import warnings
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro import obs as _obs

from . import epilogue as _epi
from . import opope_gemm as _kern
from . import opope_grouped as _gkern
from . import ref as _ref

__all__ = [
    "LayerWeight",
    "materialize",
    "matmul",
    "grouped_matmul",
    "linear",
    "epilogue_capable",
    "fusion_source",
    "default_backend",
    "set_default_backend",
    "register_backend",
    "resolve_backend",
    "resolve_grouped_backend",
    "available_backends",
    "registered_backends",
    "grouped_backends",
    "grad_backend_of",
    "fallback_chain_of",
    "family_of",
    "tunable_backends",
    "tile_for",
    "tile_source",
    "heuristic_tile",
    "tile_cache_info",
    "tile_cache_stats",
    "reset_tile_cache_stats",
    "on_miss_streak",
    "on_util_gap",
    "clear_tile_cache",
    "capture_shapes",
]

_DEFAULT_BACKEND = "auto"


@functools.partial(
    jax.tree_util.register_dataclass, data_fields=["stack", "layer"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class LayerWeight:
    """Layer ``layer`` of a stacked ``[L, ...]`` parameter, not yet sliced.

    A serving layer scan hands these to the model's GEMM sites, so a
    ``reads_stacked`` backend reads the weight straight from the stack.
    ``shape``/``dtype`` are the layer's; :meth:`get` is the slice itself
    (``lax.dynamic_index_in_dim``, which clamps an out-of-range index)."""

    stack: jax.Array
    layer: jax.Array

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self.stack.shape[1:])

    @property
    def dtype(self):
        return self.stack.dtype

    def get(self) -> jax.Array:
        return jax.lax.dynamic_index_in_dim(
            self.stack, self.layer, keepdims=False
        )


def _is_view(x) -> bool:
    return isinstance(x, LayerWeight)


def materialize(tree):
    """``tree`` with every :class:`LayerWeight` replaced by its slice; plain
    arrays pass through unchanged. For parameters that no GEMM reads (norm
    scales, biases, recurrent-mixer weights)."""
    return jax.tree.map(
        lambda x: x.get() if _is_view(x) else x, tree, is_leaf=_is_view
    )

# --------------------------------------------------------------------------
# Backend registry
# --------------------------------------------------------------------------

# A backend is fn(a, b, c_or_None, out_dtype) -> [M, N] array with fp32
# accumulation and a single final cast (the repo-wide numerics contract).
BackendFn = Callable[[jax.Array, jax.Array, Optional[jax.Array], jnp.dtype], jax.Array]
# The grouped member of a backend family: fn(a [G,M,K], b [G,K,N], c_or_None,
# out_dtype) -> [G, M, N], same accumulation/cast contract per group. ``c``
# is None, a full [G, M, N] preload, or a [G, N] per-group bias row.
GroupedFn = Callable[[jax.Array, jax.Array, Optional[jax.Array], jnp.dtype], jax.Array]


@dataclasses.dataclass(frozen=True)
class _Backend:
    name: str
    fn: BackendFn
    available: Callable[[], bool]
    # Degradation order when this backend's probe fails (None = the default
    # chain). Quantized backends fall back to other *quantized* backends so
    # an unavailable accelerator path degrades without changing numerics.
    fallback: Optional[Tuple[str, ...]] = None
    # Backend for the custom_vjp backward GEMMs (None = same as forward).
    # Quantized backends set a full-precision grad backend — the paper's
    # "training still needs FP" rule, enforced at the registry.
    grad_backend: Optional[str] = None
    # Grouped/batched GEMM implementation (None = this backend has no grouped
    # member; grouped_matmul degrades along the fallback chain to one that
    # does).
    grouped: Optional[GroupedFn] = None
    # Separate availability probe for the grouped member (None = the grouped
    # member is available whenever the backend is). Per-member probing keeps
    # a grouped-only lowering failure from disabling the 2-D matmul path:
    # dense models keep their compiled kernels, only grouped_matmul degrades.
    grouped_available: Optional[Callable[[], bool]] = None
    # Numerics family ("fp" full-precision, "q8" int8-quantized, ...): the
    # invariant a fallback chain must preserve — degradation may change the
    # execution engine, never the numerics family.
    family: str = "fp"
    # Block-shape heuristic fn(m, k, n, elem_bytes=...) -> (bm, bn, bk) for
    # backends whose kernels take block_*= parameters. None = the backend has
    # no tile knob (the XLA paths) and is not tunable. Tuned backends resolve
    # tiles through ops._tile_for: tuning table first, this heuristic second.
    tile_fn: Optional[Callable[..., Tuple[int, int, int]]] = None
    # Whether fn/grouped accept the two extra epilogue arguments
    # (ep_steps, ep_ops) and fuse the op pipeline at the accumulator
    # writeback. Backends without it (the XLA references) get the post-hoc
    # lane in _matmul_impl/_grouped_impl — same numerics, same single cast.
    epilogue_fused: bool = False
    # Whether fn accepts a LayerWeight as b and reads that layer in place.
    # matmul() hands the other backends the layer's slice instead.
    reads_stacked: bool = False


_REGISTRY: Dict[str, _Backend] = {}
# Default degradation order when a requested backend's availability probe
# fails: prefer the semantics-preserving interpreter, then the XLA reference.
_FALLBACK_CHAIN = ("pallas_interpret", "xla")


def register_backend(
    name: str,
    fn: BackendFn,
    *,
    available: Union[bool, Callable[[], bool]] = True,
    fallback: Optional[Tuple[str, ...]] = None,
    grad_backend: Optional[str] = None,
    grouped: Optional[GroupedFn] = None,
    grouped_available: Optional[Union[bool, Callable[[], bool]]] = None,
    family: str = "fp",
    tile_fn: Optional[Callable[..., Tuple[int, int, int]]] = None,
    epilogue_fused: bool = False,
    reads_stacked: bool = False,
) -> None:
    """Register (or replace) a matmul backend.

    ``available`` is either a bool or a zero-arg probe evaluated lazily at
    resolution time (never at import — see :func:`_pallas_compiles`).
    ``fallback`` overrides the default degradation chain for this backend;
    ``grad_backend`` names the backend the custom_vjp backward GEMMs run on
    (quantized backends point it at a full-precision path). ``grouped`` is
    the backend family's grouped/batched GEMM member (``[G,M,K] @ [G,K,N]``)
    served by :func:`grouped_matmul`, with its own optional
    ``grouped_available`` probe (default: available whenever the backend
    is) so a grouped-only failure never disables the 2-D path; ``family``
    names the numerics family (``"fp"``/``"q8"``) a degradation chain must
    preserve. ``tile_fn`` is the block-shape heuristic
    ``fn(m, k, n, elem_bytes=...) -> (bm, bn, bk)`` for kernels with
    ``block_*=`` knobs — registering one makes the backend tunable: its
    tiles resolve through the tuning table (:mod:`repro.tune`) before this
    heuristic. ``epilogue_fused=True`` declares that ``fn``/``grouped``
    accept ``(a, b, c, out_dtype, ep_steps, ep_ops)`` and fuse the epilogue
    pipeline at the accumulator writeback; backends without it are served by
    the numerically-identical post-hoc lane. ``reads_stacked=True`` declares
    that ``fn`` accepts a :class:`LayerWeight` as ``b`` and reads the layer
    in place; other backends are handed its slice.
    """
    if not callable(fn):
        raise TypeError(f"backend fn for {name!r} is not callable")
    probe = available if callable(available) else (lambda _a=bool(available): _a)
    gprobe = (
        grouped_available
        if grouped_available is None or callable(grouped_available)
        else (lambda _a=bool(grouped_available): _a)
    )
    _REGISTRY[name] = _Backend(
        name, fn, probe, fallback=tuple(fallback) if fallback else None,
        grad_backend=grad_backend, grouped=grouped, grouped_available=gprobe,
        family=family, tile_fn=tile_fn, epilogue_fused=epilogue_fused,
        reads_stacked=reads_stacked,
    )


def registered_backends() -> List[str]:
    return list(_REGISTRY)


def available_backends() -> List[str]:
    _load_plugin_backends()  # the quant backends count, even if not yet named
    return [n for n, b in _REGISTRY.items() if _probe_ok(b)]


def grouped_backends() -> List[str]:
    """Names of registered backends that declare a grouped GEMM member
    (regardless of the grouped probe's outcome on this platform)."""
    _load_plugin_backends()
    return [n for n, b in _REGISTRY.items() if b.grouped is not None]


def fallback_chain_of(name: str) -> Tuple[str, ...]:
    """The degradation chain a backend resolves along when unavailable."""
    _load_plugin_backends()
    b = _REGISTRY.get(name)
    if b is None:
        raise ValueError(
            f"unknown matmul backend {name!r}; registered: {registered_backends()}"
        )
    return b.fallback or _FALLBACK_CHAIN


def family_of(name: str) -> str:
    """Numerics family of a backend ("fp", "q8"): what degradation preserves."""
    _load_plugin_backends()
    b = _REGISTRY.get(name)
    if b is None:
        raise ValueError(
            f"unknown matmul backend {name!r}; registered: {registered_backends()}"
        )
    return b.family


def _platform() -> str:
    """Platform of the default device ("tpu", "cpu", ...). Called lazily at
    resolution time, never at import (see :func:`_pallas_compiles`)."""
    return jax.devices()[0].platform


def _run_probe(probe: Callable[[], bool]) -> bool:
    """Run an availability probe. Off the TPU a probe that raises counts as
    "unavailable"; on a TPU the error propagates — a compiler refusal there
    is a fault to show, not a reason to run somewhere else."""
    try:
        return bool(probe())
    except Exception:
        if _platform() == "tpu":
            raise
        return False


def _probe_ok(backend: _Backend) -> bool:
    return _run_probe(backend.available)


def _grouped_ok(backend: _Backend) -> bool:
    """Whether the backend's grouped member is usable (declared + probed)."""
    if backend.grouped is None:
        return False
    if backend.grouped_available is None:
        return True
    return _run_probe(backend.grouped_available)


def _fallback_target_ok(name: str) -> bool:
    """Interpreter backends are degradation targets off the TPU only: on a
    TPU they would run every kernel in the Pallas interpreter on the host.
    They stay resolvable when requested by name."""
    return not (name.endswith("_interpret") and _platform() == "tpu")


def _compile_probe(what: str, lower) -> bool:
    """Compile ``lower()`` (a ``jax.stages.Lowered``) as an availability
    probe on a TPU. A refusal raises with the compiler's message instead of
    quietly sending every GEMM to another backend."""
    try:
        lower().compile()
    except Exception as e:
        raise RuntimeError(
            f"compiled {what} failed to compile on this TPU: {e}"
        ) from e
    return True


@functools.lru_cache(maxsize=None)
def _pallas_compiles() -> bool:
    """Probe once whether the *compiled* Pallas path lowers here.

    Lazy (first ``auto``/``pallas`` resolution, not import) because touching
    ``jax.devices()`` at import would lock the device count before the
    dry-run can set ``XLA_FLAGS``. Off the TPU the answer is "unavailable";
    on a TPU a tiny one-tile GEMM is lowered and compiled, and a failure
    raises (:func:`_compile_probe`).
    """
    if _platform() != "tpu":
        return False
    a = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    return _compile_probe(
        "Pallas GEMM", lambda: _kern.opope_gemm.lower(a, b, interpret=False)
    )


@functools.lru_cache(maxsize=None)
def _pallas_grouped_compiles() -> bool:
    """Probe once whether the compiled grouped (G, m, n, k) grid lowers here
    (a separate probe from :func:`_pallas_compiles`, same rules)."""
    if not _pallas_compiles():
        return False
    ag = jax.ShapeDtypeStruct((2, 8, 128), jnp.float32)
    bg = jax.ShapeDtypeStruct((2, 128, 128), jnp.float32)
    return _compile_probe(
        "grouped Pallas GEMM",
        lambda: _gkern.opope_gemm_grouped.lower(ag, bg, interpret=False),
    )


# Cap on the per-(backend, family, M, N, K, G, dtype) tile-selection memo. A
# training run sees a handful of layer shapes, but a long-lived serving
# process sees an unbounded stream of (prompt-bucket x layer) shapes; LRU
# eviction keeps the memo from growing without limit while still making
# repeated shapes free.
_TILE_CACHE_CAP = 512

# Lazily loaded tuning-table state (repro.tune.table.TuningTable or None).
# Loaded once on the first tile resolution, dropped by clear_tile_cache() —
# so a test (or a process that just ran the tuner) can point REPRO_TUNE_TABLE
# somewhere else and have the next resolution pick it up.
_TUNE_STATE: Dict[str, object] = {"loaded": False, "table": None}


def _tuning_table():
    if not _TUNE_STATE["loaded"]:
        _TUNE_STATE["loaded"] = True
        try:
            from repro.tune.table import load_active_table

            _TUNE_STATE["table"] = load_active_table()
        except Exception:  # tune package absent/broken: heuristics only
            _TUNE_STATE["table"] = None
    return _TUNE_STATE["table"]


def _tuned_tile(
    backend: Optional[str], family: str, m: int, k: int, n: int,
    groups: int, itemsize: int,
) -> Optional[Tuple[int, int, int]]:
    """Tuning-table lookup, validated against the kernel's hard constraints.

    A table entry is untrusted input (hand-edited file, stale kernel
    revision): an illegal block shape falls back to the heuristic with a
    warning instead of reaching a ``pallas_call``.
    """
    if backend is None:
        return None
    b = _REGISTRY.get(backend)
    if b is None or b.tile_fn is None:
        return None  # no tile knob: a table entry for this name is inert
    table = _tuning_table()
    if table is None:
        return None
    tile = table.lookup(
        backend=backend, shape_family=family, m=m, k=k, n=n, g=groups,
        itemsize=itemsize,
    )
    if tile is None:
        return None
    m_align = 32 if b.family == "q8" else 8
    if not _kern.validate_block_shape(
        tile[0], tile[1], tile[2], elem_bytes=itemsize, m_align=m_align
    ):
        warnings.warn(
            f"tuning-table entry {tile} for backend {backend!r} "
            f"({family} {m}x{k}x{n}, g={groups}) violates kernel constraints; "
            "using the heuristic instead",
            RuntimeWarning,
            stacklevel=3,
        )
        return None
    return tile


# Resettable tile-lookup telemetry (distinct from the lru memo's own
# CacheInfo, whose hit/miss totals cannot be zeroed without dropping the
# memo): hits/misses feed the ``tile.lookups`` counter, the consecutive-miss
# streak feeds the ``on_miss_streak`` auto-retune seam (ROADMAP item 4).
_TILE_STATS_LOCK = threading.Lock()
_TILE_STATS = {"hits": 0, "misses": 0, "streak": 0}
# callback fn(key, streak) fired when the miss streak reaches the threshold
# (and again at each further multiple while it persists). ``None`` routes to
# the default repro.tune hook, which logs a "retune candidate" event.
_MISS_STREAK_HOOK: Dict[str, object] = {"fn": None, "threshold": 8}

# The key a miss-streak callback receives: everything the tuner needs to
# reproduce (and tune) the shape that keeps missing the memo/table.
TileKey = Tuple[Optional[str], str, int, int, int, int, int]


def on_miss_streak(
    callback: Optional[Callable[[TileKey, int], None]] = None,
    *,
    threshold: int = 8,
) -> None:
    """Register the sustained tile-cache-miss callback (the auto-retune seam).

    ``callback(key, streak)`` fires when ``threshold`` consecutive tile
    resolutions miss the memo — the signature of a long-lived process seeing
    a shape stream the tuning table doesn't cover — and again at every
    further multiple while the streak persists. ``key`` is ``(backend,
    shape_family, m, k, n, groups, itemsize)``. ``callback=None`` restores
    the default hook (``repro.tune.retune``: count + log the retune
    candidate, never retune implicitly). Exceptions in the callback are
    swallowed: a telemetry hook must never break tile resolution.
    """
    if threshold < 1:
        raise ValueError("miss-streak threshold must be >= 1")
    _MISS_STREAK_HOOK["fn"] = callback
    _MISS_STREAK_HOOK["threshold"] = int(threshold)


def _default_miss_streak(key: TileKey, streak: int) -> None:
    try:
        from repro.tune.retune import retune_candidate
    except Exception:
        return
    retune_candidate(key, streak)


def _note_tile_lookup(missed: bool, key: TileKey) -> None:
    with _TILE_STATS_LOCK:
        if missed:
            _TILE_STATS["misses"] += 1
            _TILE_STATS["streak"] += 1
            streak = _TILE_STATS["streak"]
        else:
            _TILE_STATS["hits"] += 1
            _TILE_STATS["streak"] = 0
            streak = 0
    if _obs.enabled():
        _obs.counter(
            "tile.lookups", result="miss" if missed else "hit"
        ).inc()
    if missed:
        thr = int(_MISS_STREAK_HOOK["threshold"])  # type: ignore[arg-type]
        if streak >= thr and streak % thr == 0:
            fn = _MISS_STREAK_HOOK["fn"] or _default_miss_streak
            try:
                fn(key, streak)  # type: ignore[operator]
            except Exception:
                pass


# The drift sibling of the miss-streak seam (ROADMAP item 4): on_miss_streak
# sees shapes the tuning table MISSES; on_util_gap sees shapes the table
# COVERS whose live roofline fraction (obs.attr attribution) keeps landing
# below a threshold — a tuned entry gone stale (new jax version, different
# device, workload drift). Same contract: fires at streak multiples,
# exceptions swallowed, None restores the default repro.tune hook.
_UTIL_GAP_HOOK: Dict[str, object] = {"fn": None, "threshold": 0.5, "streak": 4}
_UTIL_STREAKS: Dict[TileKey, int] = {}


def on_util_gap(
    callback: Optional[Callable[[TileKey, int, float], None]] = None,
    *,
    threshold: float = 0.5,
    streak: int = 4,
) -> None:
    """Register the tuned-but-underperforming callback (the drift-retune seam).

    Fed by :func:`repro.obs.attr.observe_step`: every attributed execution
    of a *tuned* GEMM class scores a roofline fraction; when a key's
    fraction falls below ``threshold`` x its own best observed fraction for
    ``streak`` consecutive observations, ``callback(key, streak_len,
    fraction)`` fires (and again at every further multiple while the gap
    persists). Relative-to-own-best, not absolute: a CPU run scores ~1e-4
    of the TPU-v5e roofline while being perfectly healthy — drift is a
    shape performing worse than *itself*, which is exactly the signature of
    a stale tuning-table entry. ``callback=None`` restores the default hook
    (``repro.tune.retune.retune_candidate(..., reason="util_gap")``: count +
    log, never retune implicitly). Exceptions in the callback are swallowed.
    Heuristic-tiled observations reset the streak only — an untuned shape is
    the miss-streak seam's business, not this one's.
    """
    if not (0.0 < threshold <= 1.0):
        raise ValueError("util-gap threshold must be in (0, 1]")
    if streak < 1:
        raise ValueError("util-gap streak must be >= 1")
    _UTIL_GAP_HOOK["fn"] = callback
    _UTIL_GAP_HOOK["threshold"] = float(threshold)
    _UTIL_GAP_HOOK["streak"] = int(streak)


def _default_util_gap(key: TileKey, streak: int, fraction: float) -> None:
    try:
        from repro.tune.retune import retune_candidate
    except Exception:
        return
    retune_candidate(key, streak, reason="util_gap")


# Best roofline fraction ever observed per tuned key: the self-relative
# baseline the gap test compares against.
_UTIL_BEST: Dict[TileKey, float] = {}


def _note_util_observation(key: TileKey, fraction: float, source: str) -> None:
    """One attributed utilization observation for ``key`` (obs.attr calls
    this). Only tuned tiles advance the gap streak."""
    if source != "tuned":
        _UTIL_STREAKS.pop(key, None)
        return
    with _TILE_STATS_LOCK:
        best = _UTIL_BEST.get(key, 0.0)
        if fraction > best:
            _UTIL_BEST[key] = fraction
            best = fraction
        thr = float(_UTIL_GAP_HOOK["threshold"])  # type: ignore[arg-type]
        if best > 0.0 and fraction < thr * best:
            streak = _UTIL_STREAKS.get(key, 0) + 1
            _UTIL_STREAKS[key] = streak
        else:
            _UTIL_STREAKS.pop(key, None)
            return
    if _obs.enabled():
        _obs.counter("gemm.util_gap_observations").inc()
    need = int(_UTIL_GAP_HOOK["streak"])  # type: ignore[arg-type]
    if streak >= need and streak % need == 0:
        fn = _UTIL_GAP_HOOK["fn"] or _default_util_gap
        try:
            fn(key, streak, fraction)  # type: ignore[operator]
        except Exception:
            pass


class _TileResolver:
    """The memoized block-shape resolver behind ``ops._tile_for``.

    Drop-in for the plain ``lru_cache`` it replaces (``cache_info`` /
    ``cache_clear`` keep their semantics) plus lookup telemetry: every call
    notes hit-or-miss into the resettable stats + the ``tile.lookups``
    counter and advances the miss streak (:func:`on_miss_streak`).

    The memo key carries the shape family and group count (a grouped GEMM
    must never share a memo slot — or a tuning-table entry — with a dense
    GEMM of the same (M, K, N): their pipelining behaviour differs) and the
    backend name, because tuned winners are measured per backend.
    Resolution order: tuned table first, the backend's ``tile_fn``
    heuristic second.
    """

    def __init__(self, maxsize: int) -> None:
        self._cached = functools.lru_cache(maxsize=maxsize)(self._resolve)

    @staticmethod
    def _resolve(
        m: int, k: int, n: int, itemsize: int, family: str, groups: int,
        backend: Optional[str],
    ) -> Tuple[int, int, int]:
        tuned = _tuned_tile(backend, family, m, k, n, groups, itemsize)
        if tuned is not None:
            return tuned
        b = _REGISTRY.get(backend) if backend else None
        tile_fn = b.tile_fn if (b is not None and b.tile_fn is not None) else (
            _kern.default_block_shape
        )
        return tile_fn(m, k, n, elem_bytes=itemsize)

    def __call__(
        self,
        m: int,
        k: int,
        n: int,
        itemsize: int,
        family: str = "dense",
        groups: int = 0,
        backend: Optional[str] = None,
    ) -> Tuple[int, int, int]:
        before = self._cached.cache_info().misses
        tile = self._cached(m, k, n, itemsize, family, groups, backend)
        missed = self._cached.cache_info().misses != before
        _note_tile_lookup(
            missed, (backend, family, m, k, n, groups, itemsize)
        )
        return tile

    def cache_info(self):
        return self._cached.cache_info()

    def cache_clear(self) -> None:
        self._cached.cache_clear()


_tile_for = _TileResolver(maxsize=_TILE_CACHE_CAP)


def tile_cache_info():
    """CacheInfo for the tile-selection memo (currsize never exceeds the cap).

    Lifetime totals of the underlying LRU — for *resettable* counters (the
    cross-test-bleed-safe surface) use :func:`tile_cache_stats`."""
    return _tile_for.cache_info()


def tile_cache_stats() -> Dict[str, int]:
    """Resettable tile-lookup stats: ``hits``/``misses`` since the last
    :func:`reset_tile_cache_stats`, the current consecutive ``miss_streak``,
    and the memo's ``currsize``/``maxsize``."""
    info = _tile_for.cache_info()
    with _TILE_STATS_LOCK:
        return {
            "hits": _TILE_STATS["hits"],
            "misses": _TILE_STATS["misses"],
            "miss_streak": _TILE_STATS["streak"],
            "currsize": info.currsize,
            "maxsize": info.maxsize,
        }


def reset_tile_cache_stats() -> None:
    """Zero the resettable lookup counters and the miss streak WITHOUT
    touching the memo itself (tests call this between cases so counts can't
    leak across suite order; warm tiles stay warm)."""
    with _TILE_STATS_LOCK:
        _TILE_STATS["hits"] = 0
        _TILE_STATS["misses"] = 0
        _TILE_STATS["streak"] = 0
        _UTIL_STREAKS.clear()
        _UTIL_BEST.clear()


def clear_tile_cache() -> None:
    """Drop the tile memo, the epilogue-fusion memo AND the loaded
    tuning-table state: the next tile resolution re-reads the table from
    ``REPRO_TUNE_TABLE`` / the default location. The miss streak resets too
    (post-clear misses are expected, not a retune signal)."""
    _tile_for.cache_clear()
    _fusion_for.cache_clear()
    _TUNE_STATE["loaded"] = False
    _TUNE_STATE["table"] = None
    with _TILE_STATS_LOCK:
        _TILE_STATS["streak"] = 0


def tunable_backends() -> List[str]:
    """Registered backends with a tile knob (a ``tile_fn``): the set the
    ``repro-tune`` CLI offers to tune."""
    _load_plugin_backends()
    return [n for n, b in _REGISTRY.items() if b.tile_fn is not None]


def _tile_itemsize(backend: str, dtype) -> int:
    """Element width the backend's tile selection keys on: q8 backends
    stream int8 panels whatever the caller-visible dtype."""
    b = _REGISTRY.get(backend)
    if b is not None and b.family == "q8":
        return 1
    return jnp.dtype(dtype).itemsize


def tile_for(
    backend: str, m: int, k: int, n: int, *, groups: int = 0,
    dtype=jnp.float32,
) -> Tuple[int, int, int]:
    """The (bm, bn, bk) block shape ``backend`` would run this GEMM with
    (``groups=0`` = the dense 2-D family, ``groups>0`` = the grouped family
    where (m, k, n) is the per-group shape)."""
    _load_plugin_backends()
    family = "grouped" if groups else "dense"
    return _tile_for(
        m, k, n, _tile_itemsize(backend, dtype),
        family=family, groups=groups, backend=backend,
    )


def tile_source(
    backend: str, m: int, k: int, n: int, *, groups: int = 0,
    dtype=jnp.float32,
) -> str:
    """``"tuned"`` if the tuning table decides this shape's blocks,
    ``"heuristic"`` if the backend's ``tile_fn`` does (including backends
    with no tile knob at all — the XLA paths always report heuristic)."""
    _load_plugin_backends()
    family = "grouped" if groups else "dense"
    tuned = _tuned_tile(
        backend, family, m, k, n, groups, _tile_itemsize(backend, dtype)
    )
    return "tuned" if tuned is not None else "heuristic"


def heuristic_tile(
    backend: str, m: int, k: int, n: int, *, dtype=jnp.float32
) -> Tuple[int, int, int]:
    """The backend's ``tile_fn`` choice, bypassing any loaded tuning table —
    the baseline column of ``BENCH_kernels.json``."""
    _load_plugin_backends()
    b = _REGISTRY.get(backend)
    itemsize = _tile_itemsize(backend, dtype)
    fn = b.tile_fn if (b is not None and b.tile_fn is not None) else (
        _kern.default_block_shape
    )
    return fn(m, k, n, elem_bytes=itemsize)


# ---------------------------------------------------------------------------
# Epilogue fusion decision (tuned verdict first, fuse-by-default second)
# ---------------------------------------------------------------------------


def epilogue_capable(name: str) -> bool:
    """Whether ``name``'s kernels fuse epilogues at the accumulator writeback
    (``epilogue_fused`` registration). Incapable backends still serve every
    ``epilogue=`` request through the post-hoc lane — this only reports
    *where* the pipeline runs."""
    _load_plugin_backends()
    b = _REGISTRY.get(name)
    if b is None:
        raise ValueError(
            f"unknown matmul backend {name!r}; registered: {registered_backends()}"
        )
    return b.epilogue_fused


@functools.lru_cache(maxsize=_TILE_CACHE_CAP)
def _fusion_for(
    m: int, k: int, n: int, itemsize: int,
    family: str = "dense", groups: int = 0, backend: Optional[str] = None,
) -> bool:
    """Memoized per-shape fuse-or-not verdict for an epilogue-capable backend.

    The tuning table's measured decision (``TuneEntry.fuse_epilogue``, written
    by :mod:`repro.tune` when it times fused vs post-hoc) wins; with no entry
    the default is to fuse — the writeback pass is free, the post-hoc pass is
    an extra HBM round trip, so fusion only loses when the epilogue operands'
    streaming perturbs the kernel's pipelining (exactly what the tuner
    measures).
    """
    table = _tuning_table()
    if table is not None:
        verdict = table.lookup_fusion(
            backend=backend, shape_family=family, m=m, k=k, n=n, g=groups,
            itemsize=itemsize,
        )
        if verdict is not None:
            return bool(verdict)
    return True


def fusion_source(
    backend: str, m: int, k: int, n: int, *, groups: int = 0,
    dtype=jnp.float32,
) -> str:
    """``"tuned"`` if the tuning table decides fused-vs-post-hoc for this
    shape on this backend, ``"default"`` if the fuse-by-default rule does
    (including backends with no fused writeback at all)."""
    _load_plugin_backends()
    family = "grouped" if groups else "dense"
    table = _tuning_table()
    if table is not None:
        verdict = table.lookup_fusion(
            backend=backend, shape_family=family, m=m, k=k, n=n, g=groups,
            itemsize=_tile_itemsize(backend, dtype),
        )
        if verdict is not None:
            return "tuned"
    return "default"


# ---------------------------------------------------------------------------
# Shape capture (the tuner's workload-harvest hook)
# ---------------------------------------------------------------------------

# When capture is active, every matmul/grouped_matmul records
# (shape_family, m, k, n, g, dtype_name) at trace time. Harvesting a model's
# GEMM workload is then one jax.eval_shape of its loss/prefill under
# capture_shapes() — zero FLOPs, exact shapes (repro.tune.capture).
_SHAPE_CAPTURE: List[list] = []


class capture_shapes:
    """Context manager recording every GEMM shape routed through the registry.

    Yields a list of ``(shape_family, m, k, n, g, dtype_name)`` tuples in
    call order (duplicates included — callers dedupe). Nestable; tracing
    (``jax.eval_shape`` / ``jit``) triggers the records, so no compute is
    needed to harvest a workload.
    """

    def __enter__(self):
        self._records: List[Tuple[str, int, int, int, int, str]] = []
        _SHAPE_CAPTURE.append(self._records)
        return self._records

    def __exit__(self, *exc):
        # Remove by identity, not equality: two nested captures with equal
        # contents (e.g. both empty) must each detach their OWN list.
        for i in range(len(_SHAPE_CAPTURE) - 1, -1, -1):
            if _SHAPE_CAPTURE[i] is self._records:
                del _SHAPE_CAPTURE[i]
                break
        return False


def _record_shape(family: str, m: int, k: int, n: int, g: int, dtype) -> None:
    if _SHAPE_CAPTURE:
        rec = (family, int(m), int(k), int(n), int(g), jnp.dtype(dtype).name)
        for records in _SHAPE_CAPTURE:
            records.append(rec)


def _note_gemm_call(
    shape_family: str, backend: str, m: int, k: int, n: int, groups: int,
    dtype, b_dtype=None, out_dtype=None, b_layout: str = "array",
) -> None:
    """Count one GEMM entry-point call into ``gemm.calls``.

    Labels carry the resolved backend, its numerics family, the shape
    family (dense/grouped), whether B is read in place from a stacked
    parameter (``b=stacked``) or arrives as an array (``b=array``), and —
    the introspection the autotuner feeds on — whether the tile and the
    fusion verdict came from the tuned table or the heuristic/default.
    When an :class:`repro.obs.attr.capture_gemms` bracket is active, the
    same facts (plus the actual operand dtypes, for honest byte accounting)
    are appended as a :class:`GemmRecord` so a timed span owner can
    attribute its measured step time. Host-side only: inside ``jit`` this
    runs once at trace time, never per step."""
    if not _obs.enabled():
        return
    b = _REGISTRY.get(backend)
    itemsize = jnp.dtype(dtype).itemsize
    tile = "tuned" if _tuned_tile(
        backend, shape_family, m, k, n, groups, itemsize
    ) is not None else "heuristic"
    fusion = "none"
    if b is not None and b.epilogue_fused:
        table = _tuning_table()
        verdict = None
        if table is not None:
            verdict = table.lookup_fusion(
                backend=backend, shape_family=shape_family, m=m, k=k, n=n,
                g=groups, itemsize=itemsize,
            )
        fusion = "tuned" if verdict is not None else "default"
    _obs.counter(
        "gemm.calls",
        b=b_layout,
        backend=backend,
        family=b.family if b is not None else "?",
        shape=shape_family,
        tile=tile,
        fusion=fusion,
    ).inc()
    if _obs.attr.capturing():
        _obs.attr.record_call(_obs.attr.GemmRecord(
            shape_family=shape_family,
            backend=backend,
            family=b.family if b is not None else "?",
            m=int(m), k=int(k), n=int(n), g=int(groups),
            a_dtype=jnp.dtype(dtype).name,
            b_dtype=jnp.dtype(b_dtype if b_dtype is not None else dtype).name,
            out_dtype=jnp.dtype(
                out_dtype if out_dtype is not None else dtype
            ).name,
            tile_source=tile,
            tile_key=(
                backend, shape_family, int(m), int(k), int(n), int(groups),
                _tile_itemsize(backend, dtype),
            ),
        ))


def _maybe_audit_gemm(kind, backend, out, ref_fn, m, k, n, g=0):
    """Shadow-audit hook for quantized-family entry-point calls.

    Cheap rejections first (fp family, tracer output, metrics off) so the
    non-audited hot path pays a couple of host-side branches; the sampling
    gate itself lives in :mod:`repro.obs.audit`. Runs only on concrete
    outputs — inside ``jit`` the output is a tracer and the call is a no-op,
    which is what keeps the compiled HLO bit-identical with auditing on or
    off (the PR 7 zero-cost contract)."""
    if not _obs.enabled():
        return
    fam = family_of(backend)
    if fam == "fp":
        return
    if isinstance(out, jax.core.Tracer):
        return
    _obs.audit.maybe_audit_gemm(
        kind=kind, backend=backend, family=fam, out=out, ref_fn=ref_fn,
        m=int(m), k=int(k), n=int(n), g=int(g),
    )


def _note_degradation(
    requested: str, resolved: str, reason: str, hop: int
) -> None:
    """Telemetry twin of the degradation warning: a counter (labelled by
    requested/resolved backend and reason) plus a structured event carrying
    the fallback-chain hop index."""
    if not _obs.enabled():
        return
    _obs.counter(
        "gemm.degradations",
        requested=requested,
        resolved=resolved,
        reason=reason,
    ).inc()
    _obs.event(
        "degradation",
        requested=requested,
        resolved=resolved,
        reason=reason,
        hop=hop,
    )


def _pallas_fn(interpret: bool) -> BackendFn:
    name = "pallas_interpret" if interpret else "pallas"

    def run(a, b, c, out_dtype, ep_steps=(), ep_ops=()):
        bm, bn, bk = _tile_for(
            a.shape[0], a.shape[1], b.shape[1], jnp.dtype(a.dtype).itemsize,
            family="dense", backend=name,
        )
        if _is_view(b):
            return _kern.opope_gemm_stacked(
                a, b.stack, b.layer, c,
                block_m=bm, block_n=bn, block_k=bk,
                out_dtype=out_dtype, interpret=interpret,
                epilogue=ep_steps, epilogue_operands=ep_ops,
            )
        return _kern.opope_gemm(
            a, b, c,
            block_m=bm, block_n=bn, block_k=bk,
            out_dtype=out_dtype, interpret=interpret,
            epilogue=ep_steps, epilogue_operands=ep_ops,
        )

    return run


def _pallas_grouped_fn(interpret: bool) -> GroupedFn:
    name = "pallas_interpret" if interpret else "pallas"

    def run(a, b, c, out_dtype, ep_steps=(), ep_ops=()):
        # Every group shares (M, K, N): tile selection is the single-group
        # choice, through the same bounded memo as the 2-D path — but under
        # the grouped family key (and group count), so a tuned grouped entry
        # never collides with a dense entry of the same per-group shape.
        bm, bn, bk = _tile_for(
            a.shape[1], a.shape[2], b.shape[2], jnp.dtype(a.dtype).itemsize,
            family="grouped", groups=a.shape[0], backend=name,
        )
        return _gkern.opope_gemm_grouped(
            a, b, c,
            block_m=bm, block_n=bn, block_k=bk,
            out_dtype=out_dtype, interpret=interpret,
            epilogue=ep_steps, epilogue_operands=ep_ops,
        )

    return run


def _xla_fn(a, b, c, out_dtype):
    return _ref.reference_matmul(a, materialize(b), c, out_dtype=out_dtype)


def _xla_grouped_fn(a, b, c, out_dtype):
    return _ref.reference_grouped_matmul(a, b, c, out_dtype=out_dtype)


register_backend(
    "pallas", _pallas_fn(interpret=False), available=_pallas_compiles,
    grouped=_pallas_grouped_fn(interpret=False),
    grouped_available=_pallas_grouped_compiles,
    tile_fn=_kern.default_block_shape,
    epilogue_fused=True,
    reads_stacked=True,
)
register_backend(
    "pallas_interpret", _pallas_fn(interpret=True),
    grouped=_pallas_grouped_fn(interpret=True),
    tile_fn=_kern.default_block_shape,
    epilogue_fused=True,
    reads_stacked=True,
)
register_backend(
    "xla", _xla_fn, grouped=_xla_grouped_fn, reads_stacked=True
)


@functools.lru_cache(maxsize=None)
def _load_plugin_backends() -> None:
    """One-shot lazy import of packages that register extra backends.

    Resolving ``xla_q8``/``pallas_q8`` must not require callers to import
    :mod:`repro.quant` themselves; ``kernels`` must also not import ``quant``
    at module load (quant builds *on* the kernel layer). So the first
    resolution of an unknown name triggers the import here, once.
    """
    try:
        import repro.quant  # noqa: F401  (registers its backends on import)
    except ImportError:
        pass


def resolve_backend(name: Optional[str] = None) -> str:
    """Resolve a backend request to the name of an available backend.

    ``None`` means the process default; ``"auto"`` picks ``pallas`` on a TPU
    (whose compile probe raises if Mosaic refuses it), else ``xla``. An
    unavailable explicit request degrades along the backend's fallback chain
    (default ``pallas_interpret`` -> ``xla``) with a warning — but only onto
    members of the same numerics family (rather than silently change
    quantization behaviour, resolution raises), and on a TPU never onto an
    ``*_interpret`` backend.
    """
    name = name or _DEFAULT_BACKEND
    if name == "auto":
        # Consult the registry's probe (not _pallas_compiles directly) so a
        # re-registered "pallas" backend brings its own availability rule.
        return "pallas" if _probe_ok(_REGISTRY["pallas"]) else "xla"
    backend = _REGISTRY.get(name)
    if backend is None:
        _load_plugin_backends()
        backend = _REGISTRY.get(name)
    if backend is None:
        raise ValueError(
            f"unknown matmul backend {name!r}; registered: {registered_backends()}"
        )
    if _probe_ok(backend):
        return name
    for hop, fallback in enumerate(backend.fallback or _FALLBACK_CHAIN, 1):
        fb = _REGISTRY.get(fallback)
        # The family guard makes "degradation never changes numerics" a
        # runtime invariant, not just a registration convention: a backend
        # that inherited the default (fp) chain can never land a q8 request
        # on a full-precision engine — it raises instead.
        if (
            fallback != name
            and fb is not None
            and fb.family == backend.family
            and _fallback_target_ok(fallback)
            and _probe_ok(fb)
        ):
            warnings.warn(
                f"matmul backend {name!r} unavailable on this platform; "
                f"degrading to {fallback!r}",
                RuntimeWarning,
                stacklevel=2,
            )
            _note_degradation(name, fallback, "backend_unavailable", hop)
            return fallback
    raise RuntimeError(f"no available matmul backend (requested {name!r})")


def resolve_grouped_backend(name: Optional[str] = None) -> str:
    """Resolve a backend request to one that has a grouped GEMM member.

    The request first resolves exactly like :func:`resolve_backend`
    (availability probes, fallback chains, the ``auto`` rule); if the
    resolved backend declares no grouped implementation, resolution continues
    along its fallback chain — with the same degradation warning — to the
    first available backend that does. Chains preserve the numerics family,
    so a grouped request never silently changes quantization behaviour.
    """
    resolved = resolve_backend(name)
    backend = _REGISTRY[resolved]
    if _grouped_ok(backend):
        return resolved
    for hop, fallback in enumerate(backend.fallback or _FALLBACK_CHAIN, 1):
        fb = _REGISTRY.get(fallback)
        # Same family guard as resolve_backend: a q8 backend missing its
        # grouped member raises rather than silently running grouped GEMMs
        # full-precision through the default (fp) chain.
        if (
            fallback != resolved
            and fb is not None
            and fb.family == backend.family
            and _fallback_target_ok(fallback)
            and _grouped_ok(fb)
            and _probe_ok(fb)
        ):
            warnings.warn(
                f"matmul backend {resolved!r} has no usable grouped GEMM "
                f"member; degrading to {fallback!r}",
                RuntimeWarning,
                stacklevel=2,
            )
            _note_degradation(resolved, fallback, "no_grouped_member", hop)
            return fallback
    raise RuntimeError(
        f"no available grouped matmul backend (requested {name or resolved!r})"
    )


def grad_backend_of(name: str) -> str:
    """Backend the backward GEMMs of ``name`` run on (itself by default)."""
    b = _REGISTRY.get(name)
    return b.grad_backend if b is not None and b.grad_backend else name


def default_backend() -> str:
    return resolve_backend(None)


def set_default_backend(name: str) -> None:
    """Override backend globally (any registered name, or 'auto')."""
    global _DEFAULT_BACKEND
    if name != "auto" and name not in _REGISTRY:
        _load_plugin_backends()
    if name != "auto" and name not in _REGISTRY:
        raise ValueError(
            f"unknown matmul backend {name!r}; registered: {registered_backends()}"
        )
    _DEFAULT_BACKEND = name


# --------------------------------------------------------------------------
# matmul / linear entry points (custom_vjp keeps the backward in-dataflow)
# --------------------------------------------------------------------------


def _matmul_impl(
    a: jax.Array,
    b: jax.Array,
    c: Optional[jax.Array],
    backend: str,
    out_dtype,
    ep_steps: Tuple[str, ...] = (),
    ep_ops: Tuple[jax.Array, ...] = (),
) -> jax.Array:
    be = _REGISTRY[backend]
    if not ep_steps:
        return be.fn(a, b, c, out_dtype)
    aq = a.q if hasattr(a, "q") else a  # pre-quantized A: shapes live on .q
    if be.epilogue_fused and _fusion_for(
        aq.shape[0], aq.shape[1], b.shape[1], _tile_itemsize(backend, aq.dtype),
        family="dense", backend=backend,
    ):
        return be.fn(a, b, c, out_dtype, ep_steps, ep_ops)
    # Post-hoc lane: fp32 accumulator out of the backend, the same op
    # pipeline, the same single final cast — numerically identical to the
    # fused writeback (fp32 -> fp32 "cast" is exact), and applied for ANY
    # resolved backend, so fallback degradation can never drop or
    # double-apply a requested epilogue.
    acc = be.fn(a, b, c, jnp.float32)
    return _epi.apply_epilogue(acc, ep_steps, ep_ops).astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _matmul(a, b, c, backend, out_dtype):
    return _matmul_impl(a, b, c, backend, out_dtype)


def _matmul_fwd(a, b, c, backend, out_dtype):
    return _matmul_impl(a, b, c, backend, out_dtype), (a, b)


def _matmul_bwd(backend, out_dtype, res, g):
    a, b = res
    # Backward = two more O-POPE GEMMs in the same dataflow; gradients are
    # accumulated in fp32 and cast back to the operand dtypes. Quantized
    # forwards run their backward on their registered full-precision
    # grad_backend (gradients always stay FP).
    backend = grad_backend_of(backend)
    da = _matmul_impl(g, b.T, None, backend, a.dtype)
    db = _matmul_impl(a.T, g, None, backend, b.dtype)
    dc = g  # c enters the accumulator linearly
    return da, db, dc


_matmul.defvjp(_matmul_fwd, _matmul_bwd)


def matmul(
    a: jax.Array,
    b: jax.Array,
    c: Optional[jax.Array] = None,
    *,
    backend: Optional[str] = None,
    out_dtype=None,
    epilogue=None,
) -> jax.Array:
    """``a @ b (+ c)`` with O-POPE semantics; a: [..., K], b: [K, N].

    Leading batch dims of ``a`` are flattened into M (the engine sees one tall
    GEMM — exactly how the paper maps ML layers onto the engine, Table I).
    ``c`` is either a full C operand matching ``a``'s batch dims x N, or a
    1-D ``[N]`` bias row broadcast inside the backend at the accumulator
    preload point (never materialized as an [M, N] array).

    ``epilogue`` is a pipeline of registered post-ops — a name (``"silu"``),
    a ``(name, operand)`` pair (``("residual", x)``), or a sequence of either
    (:mod:`repro.kernels.epilogue`) — applied to the fp32 accumulator before
    the single final cast: inside the kernel on epilogue-capable backends
    (per the tuner's fused-vs-post-hoc verdict), post-hoc on the rest, with
    identical numerics either way. A ``c`` operand passed alongside an
    epilogue is folded in as the pipeline's first step.

    ``a`` may also be a pre-quantized activation (anything with ``.q`` /
    ``.scale``, e.g. ``quant.QuantizedTensor`` — the product of a
    ``requant_int8`` epilogue upstream) on a q8-family backend: the backend
    skips its A-quantization pass and consumes the int8 values directly.
    This is a serving-only lane (no custom_vjp).

    ``b`` may be a :class:`LayerWeight` (one layer of a stacked parameter),
    and ``c`` too: a ``reads_stacked`` backend reads ``b`` in place, any
    other gets its slice. That is a serving-only lane as well.
    """
    pre_q = hasattr(a, "q") and hasattr(a, "scale")
    arr = a.q if pre_q else a
    # Pre-quantized A defaults to fp32 output (the int8 storage dtype of the
    # input is not a meaningful default for the dequantized result).
    out_dtype = jnp.dtype(out_dtype or (jnp.float32 if pre_q else arr.dtype))
    backend = resolve_backend(backend)
    c = materialize(c)
    in_place = _is_view(b) and _REGISTRY[backend].reads_stacked
    if _is_view(b) and not in_place:
        b = b.get()
    batch_shape = arr.shape[:-1]
    m = 1
    for d in batch_shape:
        m *= d
    _record_shape("dense", m, arr.shape[-1], b.shape[-1], 0, arr.dtype)
    _note_gemm_call(
        "dense", backend, m, arr.shape[-1], b.shape[-1], 0, arr.dtype,
        b_dtype=b.dtype, out_dtype=out_dtype,
        b_layout="stacked" if in_place else "array",
    )
    n = b.shape[-1]
    steps, raw_ops = _epi.normalize_epilogue(epilogue)
    if steps and c is not None:
        # Fold C into the pipeline's head: C enters the accumulator linearly,
        # so preload-then-epilogue == bias/residual-step-then-rest.
        if c.ndim == 1:
            steps, raw_ops = ("bias",) + steps, (c,) + raw_ops
        else:
            steps, raw_ops = ("residual",) + steps, (c,) + raw_ops
        c = None

    if pre_q:
        if family_of(backend) != "q8":
            raise ValueError(
                f"pre-quantized activations need a q8-family backend; "
                f"{backend!r} is family {family_of(backend)!r}"
            )
        scale = jnp.asarray(a.scale)
        a2 = type(a)(
            arr.reshape(m, arr.shape[-1]),
            scale.reshape(m, 1) if scale.size == m else scale.reshape(1, 1),
        )
        ep_ops = _epi.canonicalize_operands(steps, raw_ops, n=n, m=m)
        out = _matmul_impl(a2, b, c, backend, out_dtype, steps, ep_ops)
        return out.reshape(*batch_shape, n)

    a2 = arr.reshape(m, arr.shape[-1])
    if in_place:
        # No custom_vjp: nothing differentiates a serving step, and training
        # scans the stack, so its GEMMs never see a LayerWeight.
        ep_ops = _epi.canonicalize_operands(steps, raw_ops, n=n, m=m)
        c2 = c.reshape(m, n) if c is not None and c.ndim > 1 else c
        out = _matmul_impl(a2, b, c2, backend, out_dtype, steps, ep_ops)
        return out.reshape(*batch_shape, n)
    if steps:
        ep_ops = _epi.canonicalize_operands(steps, raw_ops, n=n, m=m)
        out = _matmul_ep(a2, b, ep_ops, backend, out_dtype, steps)
        ref = lambda: _matmul_impl(  # noqa: E731
            a2, b, None, grad_backend_of(backend), out_dtype, steps, ep_ops)
    elif c is None:
        out = _matmul_nc(a2, b, backend, out_dtype)
        ref = lambda: _matmul_impl(  # noqa: E731
            a2, b, None, grad_backend_of(backend), out_dtype)
    elif c.ndim == 1:
        out = _matmul_bias(a2, b, c, backend, out_dtype)
        bias = c
        ref = lambda: _matmul_impl(  # noqa: E731
            a2, b, bias, grad_backend_of(backend), out_dtype)
    else:
        c2 = c.reshape(m, n)
        out = _matmul(a2, b, c2, backend, out_dtype)
        ref = lambda: _matmul_impl(  # noqa: E731
            a2, b, c2, grad_backend_of(backend), out_dtype)
    _maybe_audit_gemm("dense", backend, out, ref, m, arr.shape[-1], n)
    return out.reshape(*batch_shape, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul_nc(a, b, backend, out_dtype):
    return _matmul_impl(a, b, None, backend, out_dtype)


def _matmul_nc_fwd(a, b, backend, out_dtype):
    return _matmul_impl(a, b, None, backend, out_dtype), (a, b)


def _matmul_nc_bwd(backend, out_dtype, res, g):
    a, b = res
    backend = grad_backend_of(backend)
    da = _matmul_impl(g, b.T, None, backend, a.dtype)
    db = _matmul_impl(a.T, g, None, backend, b.dtype)
    return da, db


_matmul_nc.defvjp(_matmul_nc_fwd, _matmul_nc_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _matmul_bias(a, b, bias, backend, out_dtype):
    return _matmul_impl(a, b, bias, backend, out_dtype)


def _matmul_bias_fwd(a, b, bias, backend, out_dtype):
    return _matmul_impl(a, b, bias, backend, out_dtype), (a, b)


def _matmul_bias_bwd(backend, out_dtype, res, g):
    a, b = res
    backend = grad_backend_of(backend)
    da = _matmul_impl(g, b.T, None, backend, a.dtype)
    db = _matmul_impl(a.T, g, None, backend, b.dtype)
    dbias = g.sum(axis=0)  # the bias row enters every accumulator row once
    return da, db, dbias


_matmul_bias.defvjp(_matmul_bias_fwd, _matmul_bias_bwd)


# One custom_vjp covers every epilogue'd dense matmul: a C operand is folded
# into the pipeline as its first step by matmul() ("bias" for a [N] row,
# "residual" for a full operand — numerically identical, C enters the
# accumulator linearly), so no (c x epilogue) wrapper matrix is needed.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _matmul_ep(a, b, ep_ops, backend, out_dtype, ep_steps):
    return _matmul_impl(a, b, None, backend, out_dtype, ep_steps, ep_ops)


def _matmul_ep_fwd(a, b, ep_ops, backend, out_dtype, ep_steps):
    out = _matmul_impl(a, b, None, backend, out_dtype, ep_steps, ep_ops)
    return out, (a, b, ep_ops)


def _matmul_ep_bwd(backend, out_dtype, ep_steps, res, g):
    a, b, ep_ops = res
    backend = grad_backend_of(backend)
    # The fused forward never materializes the pre-epilogue accumulator, so
    # the backward recomputes it (one extra GEMM, fp32) — the standard
    # rematerialization trade for keeping the forward single-pass. Then the
    # epilogue pipeline backpropagates (STE/clip masks and broadcast
    # reductions live in epilogue_vjp) and the usual two transposed GEMMs
    # run on the fp32 cotangent of the accumulator.
    acc = _matmul_impl(a, b, None, backend, jnp.float32)
    g_acc, d_ops = _epi.epilogue_vjp(ep_steps, ep_ops, acc, g)
    da = _matmul_impl(g_acc, b.T, None, backend, a.dtype)
    db = _matmul_impl(a.T, g_acc, None, backend, b.dtype)
    d_ops = tuple(
        d.astype(o.dtype).reshape(o.shape) for d, o in zip(d_ops, ep_ops)
    )
    return da, db, d_ops


_matmul_ep.defvjp(_matmul_ep_fwd, _matmul_ep_bwd)


def linear(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    backend: Optional[str] = None,
    out_dtype=None,
    epilogue=None,
) -> jax.Array:
    """Linear layer on the O-POPE path. The [N] bias rides the C-preload
    operand — the fused epilogue the paper's accumulator preload enables for
    free — and is broadcast inside the backend, so no [M, N] copy of it is
    ever built (serving decode steps would otherwise pay O(M*N) per linear).
    ``epilogue=`` post-ops run after the bias, exactly as :func:`matmul`."""
    return matmul(
        x, w, bias, backend=backend, out_dtype=out_dtype, epilogue=epilogue
    )


# --------------------------------------------------------------------------
# grouped matmul entry point (the batched-GEMM member of each backend family)
# --------------------------------------------------------------------------


def _grouped_impl(a, b, c, backend, out_dtype, ep_steps=(), ep_ops=()):
    be = _REGISTRY[backend]
    if not ep_steps:
        return be.grouped(a, b, c, out_dtype)
    aq = a.q if hasattr(a, "q") else a
    if be.epilogue_fused and _fusion_for(
        aq.shape[1], aq.shape[2], b.shape[2], _tile_itemsize(backend, aq.dtype),
        family="grouped", groups=aq.shape[0], backend=backend,
    ):
        return be.grouped(a, b, c, out_dtype, ep_steps, ep_ops)
    # Post-hoc lane — identical numerics to the fused writeback; see
    # _matmul_impl.
    acc = be.grouped(a, b, c, jnp.float32)
    return _epi.apply_epilogue(acc, ep_steps, ep_ops).astype(out_dtype)


def _grouped_bwd_gemms(backend, res, g):
    """dA[g] = dO[g] @ B[g]^T, dB[g] = A[g]^T @ dO[g] — two more grouped
    GEMMs in the same dataflow, on the forward backend's grad backend (so a
    quantized grouped forward backpropagates full-precision, like the 2-D
    path)."""
    a, b = res
    backend = resolve_grouped_backend(grad_backend_of(backend))
    da = _grouped_impl(g, b.transpose(0, 2, 1), None, backend, a.dtype)
    db = _grouped_impl(a.transpose(0, 2, 1), g, None, backend, b.dtype)
    return da, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _grouped_nc(a, b, backend, out_dtype):
    return _grouped_impl(a, b, None, backend, out_dtype)


def _grouped_nc_fwd(a, b, backend, out_dtype):
    return _grouped_impl(a, b, None, backend, out_dtype), (a, b)


def _grouped_nc_bwd(backend, out_dtype, res, g):
    return _grouped_bwd_gemms(backend, res, g)


_grouped_nc.defvjp(_grouped_nc_fwd, _grouped_nc_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_bias(a, b, bias, backend, out_dtype):
    return _grouped_impl(a, b, bias, backend, out_dtype)


def _grouped_bias_fwd(a, b, bias, backend, out_dtype):
    return _grouped_impl(a, b, bias, backend, out_dtype), (a, b)


def _grouped_bias_bwd(backend, out_dtype, res, g):
    da, db = _grouped_bwd_gemms(backend, res, g)
    # each group's bias row enters every accumulator row of that group once
    return da, db, g.sum(axis=1)


_grouped_bias.defvjp(_grouped_bias_fwd, _grouped_bias_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_c(a, b, c, backend, out_dtype):
    return _grouped_impl(a, b, c, backend, out_dtype)


def _grouped_c_fwd(a, b, c, backend, out_dtype):
    return _grouped_impl(a, b, c, backend, out_dtype), (a, b)


def _grouped_c_bwd(backend, out_dtype, res, g):
    da, db = _grouped_bwd_gemms(backend, res, g)
    return da, db, g  # c enters the accumulator linearly


_grouped_c.defvjp(_grouped_c_fwd, _grouped_c_bwd)


# The grouped analogue of _matmul_ep: one custom_vjp for every epilogue'd
# grouped GEMM, with C folded into the pipeline head by grouped_matmul().
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped_ep(a, b, ep_ops, backend, out_dtype, ep_steps):
    return _grouped_impl(a, b, None, backend, out_dtype, ep_steps, ep_ops)


def _grouped_ep_fwd(a, b, ep_ops, backend, out_dtype, ep_steps):
    out = _grouped_impl(a, b, None, backend, out_dtype, ep_steps, ep_ops)
    return out, (a, b, ep_ops)


def _grouped_ep_bwd(backend, out_dtype, ep_steps, res, g):
    a, b, ep_ops = res
    backend = resolve_grouped_backend(grad_backend_of(backend))
    # Recompute the pre-epilogue accumulator (see _matmul_ep_bwd), backprop
    # the pipeline, then the two transposed grouped GEMMs.
    acc = _grouped_impl(a, b, None, backend, jnp.float32)
    g_acc, d_ops = _epi.epilogue_vjp(ep_steps, ep_ops, acc, g)
    da = _grouped_impl(g_acc, b.transpose(0, 2, 1), None, backend, a.dtype)
    db = _grouped_impl(a.transpose(0, 2, 1), g_acc, None, backend, b.dtype)
    d_ops = tuple(
        d.astype(o.dtype).reshape(o.shape) for d, o in zip(d_ops, ep_ops)
    )
    return da, db, d_ops


_grouped_ep.defvjp(_grouped_ep_fwd, _grouped_ep_bwd)


def grouped_matmul(
    a: jax.Array,
    b: jax.Array,
    c: Optional[jax.Array] = None,
    *,
    backend: Optional[str] = None,
    out_dtype=None,
    epilogue=None,
) -> jax.Array:
    """``O[g] = A[g] @ B[g] (+ C[g])``; a: [G, M, K], b: [G, K, N].

    The grouped/batched-GEMM entry point of the backend registry — one
    launch for a whole family of same-shape GEMMs (MoE expert FFNs run their
    per-expert SwiGLU through here). Resolution, fallback chains, precision
    policies and the ``grad_backend`` rule are shared with :func:`matmul`:
    the same backend names select the grouped member of the same family, and
    a quantized grouped forward backpropagates through full-precision
    grouped GEMMs.

    ``c`` is ``None``, a full ``[G, M, N]`` preload, or a ``[G, N]``
    per-group bias row broadcast inside the backend at the accumulator
    preload point (never materialized as ``[G, M, N]``).

    ``epilogue`` post-ops apply per group to the fp32 accumulator before the
    single cast, exactly as in :func:`matmul` — operands: scalar, ``[N]`` /
    ``[G, N]`` row, or full ``[G, M, N]``. A ``c`` alongside an epilogue is
    folded in as the pipeline's first step.
    """
    # Grouped members read no stacked weight in place: hand them the slice.
    b, c = materialize(b), materialize(c)
    if a.ndim != 3 or b.ndim != 3:
        raise ValueError(
            f"grouped_matmul wants a [G, M, K] @ [G, K, N]; got {a.shape} @ {b.shape}"
        )
    if a.shape[0] != b.shape[0] or a.shape[2] != b.shape[1]:
        raise ValueError(f"bad grouped GEMM shapes {a.shape} @ {b.shape}")
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    backend = resolve_grouped_backend(backend)
    _record_shape(
        "grouped", a.shape[1], a.shape[2], b.shape[2], a.shape[0], a.dtype
    )
    _note_gemm_call(
        "grouped", backend, a.shape[1], a.shape[2], b.shape[2], a.shape[0],
        a.dtype, b_dtype=b.dtype, out_dtype=out_dtype,
    )
    steps, raw_ops = _epi.normalize_epilogue(epilogue)
    if steps:
        if c is not None:
            # Same linear-preload folding as matmul(): [G, N] row -> "bias",
            # full [G, M, N] -> "residual" at the pipeline head.
            name = "bias" if c.ndim == 2 else "residual"
            steps, raw_ops = (name,) + steps, (c,) + raw_ops
        ep_ops = _epi.canonicalize_operands(
            steps, raw_ops, n=b.shape[2], m=a.shape[1], groups=a.shape[0]
        )
        out = _grouped_ep(a, b, ep_ops, backend, out_dtype, steps)
        ref = lambda: _grouped_impl(  # noqa: E731
            a, b, None,
            resolve_grouped_backend(grad_backend_of(backend)), out_dtype,
            steps, ep_ops)
    elif c is None:
        out = _grouped_nc(a, b, backend, out_dtype)
        ref = lambda: _grouped_impl(  # noqa: E731
            a, b, None,
            resolve_grouped_backend(grad_backend_of(backend)), out_dtype)
    else:
        out = (_grouped_bias if c.ndim == 2 else _grouped_c)(
            a, b, c, backend, out_dtype)
        ref = lambda: _grouped_impl(  # noqa: E731
            a, b, c,
            resolve_grouped_backend(grad_backend_of(backend)), out_dtype)
    if not hasattr(a, "q"):  # pre-quantized A has no fp twin to audit against
        _maybe_audit_gemm(
            "grouped", backend, out, ref,
            a.shape[1], a.shape[2], b.shape[2], g=a.shape[0],
        )
    return out
