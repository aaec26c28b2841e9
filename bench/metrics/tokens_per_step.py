"""Serving engine: tokens streamed in the traced window (host clock) over
the decode-step executions the device ran in it (trace)."""


def read(run):
    steps = run.trace.count.get("decode", 0)
    if not steps:
        return None
    return run.tokens_between(run.trace_t0, run.trace_t1) / steps
