"""Prefill: device time of the prefill executions in the traced window over
the unpadded prompt tokens they prefilled, per thousand tokens."""


def read(run):
    pf = run.trace.prefills()
    tokens = sum(n for _, n, _ in pf)
    if not tokens:
        return None
    return sum(d for d, _, _ in pf) / tokens * 1e6
