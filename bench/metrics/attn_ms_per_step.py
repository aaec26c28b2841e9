"""Attention: device time of the operations under the ``attn_core`` scope
inside decode steps, over the decode steps in the traced window."""


def read(run):
    n = run.trace.count.get("decode", 0)
    t = run.trace.scope_s.get(("decode", "attn_core"))
    return t / n * 1e3 if n and t else None
