"""Whole step: the model operations of every prompt prefilled and every
token streamed in the traced window (matmuls, attention at the live
context, the LM head where a token gets logits) over the window's seconds,
as a share of the chip's bf16 peak. The operations are the family's
``prompt_flops`` and ``token_flops``."""


def read(run):
    t0, t1 = run.trace_t0, run.trace_t1
    flops = 0.0
    for rid, times in run.times.items():
        plen = run.plen[rid]
        for j, t in enumerate(times):
            if t0 <= t < t1:
                flops += (
                    run.family.prompt_flops(run.cfg, plen) if j == 0
                    else run.family.token_flops(run.cfg, plen + j, lm_head=True)
                )
    return flops / (t1 - t0) / run.peaks.bf16_flops * 100.0 if flops else None
