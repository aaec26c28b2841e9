"""Serving engine, host side of a decode tick: device-idle time in the
traced window that lies inside the engine's ``serve.decode``,
``serve.readback``, ``serve.emit`` or ``serve.telemetry`` spans, over the
decode-step executions in the window."""

from lib import spans


def read(run):
    n = run.trace.count.get("decode", 0)
    idle = spans.idle_inside_s(run.trace, spans.STEP)
    return idle / n * 1e3 if n and idle is not None else None
