"""Decode step: device time of the decode-step executions in the traced
window over their count."""


def read(run):
    n = run.trace.count.get("decode", 0)
    return run.trace.module_s["decode"] / n * 1e3 if n else None
