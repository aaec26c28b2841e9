"""GEMM kernels in decode steps: the least time the chip could take for
every layer GEMM of the steps that the family runs through ``opope_gemm``
(its ``kernel_gemms``; the larger of operations over peak FLOP/s and bytes
over HBM bandwidth, per GEMM, at the rows that held a request) over the
device time of the ``opope_gemm`` kernels in those steps. At decode sizes
every one of these GEMMs is bound by memory (weights)."""

from lib import costs


def read(run):
    steps = run.trace.count.get("decode", 0)
    t = run.trace.gemm_s.get("decode", 0.0)
    if not steps or not t:
        return None
    rows = max(1, round(run.decode_tokens_between(run.trace_t0, run.trace_t1) / steps))
    least, _ = costs.gemms_least_seconds(run.family, run.cfg, rows, run.peaks)
    return steps * least / t * 100.0
