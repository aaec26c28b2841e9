"""Prefill, host side of a join: device-idle time in the traced window that
lies inside the engine's ``serve.admit`` or ``serve.prefill`` spans, over
the ``serve.prefill`` spans that start in the window."""

from lib import spans


def read(run):
    n = spans.starts(run.trace, "serve.prefill")
    idle = spans.idle_inside_s(run.trace, spans.JOIN)
    return idle / n * 1e3 if n and idle is not None else None
