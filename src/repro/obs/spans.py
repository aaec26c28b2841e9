"""Trace spans: named host brackets on the profiler's clock.

``span(name)`` is one context manager with two outputs:

* **profiler** — the body runs under ``jax.profiler.TraceAnnotation``, so
  the bracket lands in the same ``.xplane.pb`` as the device's operations,
  on the same clock, whenever a profile is being captured (any
  ``jax.profiler`` capture; see ``repro-stats --profile``). A device idle
  gap can then be put down to the span the host was in. ``args`` become the
  annotation's arguments: per-call numbers such as rows or tokens go there,
  never into histogram labels.
* **host** — a wall-clock timer records the bracket's duration into the
  ``span.seconds`` histogram, labelled by span name and ``labels`` (keep
  those few-valued: each distinct value makes another histogram).

A span names no device operation: ``jax.named_scope`` around the first call
of a jitted function does not reach that program's HLO metadata, so code
that wants a device-side scope calls ``jax.named_scope`` inside the traced
function itself.

With telemetry off (``REPRO_METRICS=0``) the whole thing is a bare
``yield`` — no annotation object, no timer — so a disabled process is
bit-for-bit the un-instrumented one.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Iterator, Mapping, Optional

from . import metrics as _m

__all__ = ["span"]


@contextlib.contextmanager
def span(
    name: str, args: Optional[Mapping[str, Any]] = None, **labels
) -> Iterator[None]:
    """Bracket a region: profiler annotation (with ``labels`` and ``args``)
    + ``span.seconds{name, **labels}`` wall timer."""
    if not _m.enabled():
        yield
        return
    import jax

    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **labels, **(args or {})):
            yield
    finally:
        _m.histogram("span.seconds", name=name, **labels).observe(
            time.perf_counter() - t0
        )
