"""GEMM kernels in prefill: the least time of every layer GEMM that the
family runs through ``opope_gemm`` (its ``kernel_gemms``) in each prefill
execution at its unpadded prompt tokens (the larger of operations
over peak FLOP/s and bytes over HBM bandwidth, per GEMM; at prefill sizes
most are bound by compute) over the device time of the ``opope_gemm``
kernels in those executions."""

from lib import costs


def read(run):
    least = t = 0.0
    for _, tokens, gemm_s in run.trace.prefills():
        if gemm_s:
            least += costs.gemms_least_seconds(run.family, run.cfg, tokens, run.peaks)[0]
            t += gemm_s
    return least / t * 100.0 if t else None
