"""The few JAX API uses that the substrate routes through one module.

The repo targets the one installed JAX (0.9.0, pinned in ``pyproject.toml``).
These helpers keep the call sites short and give a future JAX upgrade one
file to edit:

===============================  ==============================================
Helper                           JAX API
===============================  ==============================================
``tpu_compiler_params``          ``jax.experimental.pallas.tpu.CompilerParams``
``get_mesh_axis_types``          ``jax.sharding.AxisType``
``make_mesh``                    ``jax.make_mesh(..., axis_types=...)``
``set_mesh``                     ``jax.set_mesh``
``current_abstract_mesh``        ``jax.sharding.get_abstract_mesh``
``normalize_cost_analysis``      ``compiled.cost_analysis()`` (a dict)
``normalize_memory_analysis``    ``compiled.memory_analysis()``
===============================  ==============================================

**Repo rule (see README):** no module outside this one touches these APIs
directly. The acceptance grep for this rule is::

    grep -rn "CompilerParams\\|AxisType\\|get_abstract_mesh" src/repro \\
        --include="*.py" | grep -v compat.py   # must return no hits

Importing this module never initializes JAX device state (the dry-run sets
``XLA_FLAGS`` before the first device query and must keep that window open).
"""

from __future__ import annotations

import re
from contextlib import contextmanager
from typing import Any, Dict, Iterator, Optional, Sequence, Tuple

import jax

__all__ = [
    "jax_version",
    "tpu_compiler_params",
    "get_mesh_axis_types",
    "make_mesh",
    "set_mesh",
    "current_abstract_mesh",
    "mesh_axis_sizes",
    "normalize_cost_analysis",
    "normalize_memory_analysis",
]


def jax_version() -> Tuple[int, ...]:
    """Installed JAX version as an int tuple (dev/rc suffixes dropped)."""
    parts = []
    for p in jax.__version__.split("."):
        m = re.match(r"\d+", p)
        if not m:
            break
        parts.append(int(m.group(0)))
    return tuple(parts)


def tpu_compiler_params(
    *, dimension_semantics: Optional[Sequence[str]] = None, **kwargs: Any
):
    """Mosaic compiler-params object (``pltpu.CompilerParams``).

    Accepts the same keywords as the underlying class; ``dimension_semantics``
    is the one every kernel in the repo passes.
    """
    from jax.experimental.pallas import tpu as pltpu

    if dimension_semantics is not None:
        kwargs["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kwargs)


def get_mesh_axis_types(n_axes: int, kind: str = "auto") -> tuple:
    """``(AxisType.<kind>,) * n_axes``."""
    member = {"auto": "Auto", "explicit": "Explicit", "manual": "Manual"}[kind]
    return (getattr(jax.sharding.AxisType, member),) * n_axes


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    axis_types: Any = "auto",
    devices=None,
):
    """``jax.make_mesh`` with ``axis_types`` given as a kind name
    ("auto"/"explicit"/"manual") or an explicit tuple. The default is auto:
    ``jax.make_mesh`` itself defaults to explicit axes."""
    if isinstance(axis_types, str):
        axis_types = get_mesh_axis_types(len(axis_names), axis_types)
    kwargs: Dict[str, Any] = {"axis_types": axis_types}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(axis_shapes, axis_names, **kwargs)


@contextmanager
def set_mesh(mesh) -> Iterator[Any]:
    """Context manager installing ``mesh`` as the ambient mesh."""
    with jax.set_mesh(mesh):
        yield mesh


def current_abstract_mesh():
    """The ambient abstract mesh, or None when none is set."""
    ambient = jax.sharding.get_abstract_mesh()
    return ambient if ambient.axis_names else None


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """``{axis_name: size}`` for a Mesh or AbstractMesh (``{}`` for None)."""
    if mesh is None:
        return {}
    return dict(zip(mesh.axis_names, (int(s) for s in mesh.axis_sizes)))


def normalize_cost_analysis(compiled_or_result) -> Dict[str, float]:
    """Dict view of ``compiled.cost_analysis()``.

    Accepts either the compiled executable or the raw ``cost_analysis()``
    return value; None (backends that report nothing) becomes ``{}``.
    """
    result = compiled_or_result
    if hasattr(result, "cost_analysis"):
        result = result.cost_analysis()
    return dict(result or {})


def normalize_memory_analysis(compiled_or_stats) -> Dict[str, int]:
    """Dict view of ``compiled.memory_analysis()``; ``peak_bytes`` is the
    buffer-assignment high-water mark (``peak_memory_in_bytes``)."""
    stats = compiled_or_stats
    if hasattr(stats, "memory_analysis"):
        stats = stats.memory_analysis()
    return {
        "argument_bytes": int(stats.argument_size_in_bytes),
        "output_bytes": int(stats.output_size_in_bytes),
        "temp_bytes": int(stats.temp_size_in_bytes),
        "alias_bytes": int(stats.alias_size_in_bytes),
        "peak_bytes": max(int(stats.peak_memory_in_bytes), 0),
    }
