"""Operations and bytes of one decode GEMM and one prefill GEMM, counted
by hand; and the dense family's accounting pinned to the values it had
before it moved into ``reference/dense.py``."""

import json
import os

import pytest

from conftest import BENCH
from lib import costs, peaks
from reference import dense

GLM = {"n_layers": 28, "d_model": 4096, "n_heads": 32, "n_kv": 2, "head_dim": 128,
       "d_ff": 13696, "vocab": 65024, "norm": "rmsnorm", "tie_embeddings": False}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["config"]


def test_decode_gate_gemm_by_hand():
    # gate projection at M=32 slots: [32, 4096] @ [4096, 13696], silu(.)*up
    # in the epilogue reads up [32, 13696]; bf16 throughout.
    g = [x for x in dense.kernel_gemms(GLM) if x.name == "gate"][0]
    assert (g.k, g.n, g.extra_mn, g.layers) == (4096, 13696, 1, 28)
    assert costs.gemm_flops(32, g.k, g.n) == 2 * 32 * 4096 * 13696 == 3_590_324_224
    want = 2 * (32 * 4096 + 4096 * 13696 + 2 * 32 * 13696)
    assert costs.gemm_bytes(32, g.k, g.n, g.extra_mn) == want == 114_212_864
    t, bound = costs.least_seconds(3_590_324_224, want, peaks.TPU_V5E)
    assert bound == "memory" and t == pytest.approx(want / 819e9)


def test_prefill_q_gemm_by_hand():
    # Q projection of a 2048-token prompt: [2048, 4096] @ [4096, 4096].
    g = [x for x in dense.kernel_gemms(GLM) if x.name == "q"][0]
    flops = 2 * 2048 * 4096 * 4096
    assert costs.gemm_flops(2048, g.k, g.n) == flops == 68_719_476_736
    nbytes = 2 * (2048 * 4096 + 4096 * 4096 + 2048 * 4096)
    assert costs.gemm_bytes(2048, g.k, g.n) == nbytes == 67_108_864
    t, bound = costs.least_seconds(flops, nbytes, peaks.TPU_V5E)
    assert bound == "compute" and t == pytest.approx(flops / 197e12)
    # the K projection of the same prompt (N = 256) is bound by memory
    k = [x for x in dense.kernel_gemms(GLM) if x.name == "k"][0]
    nbytes = 2 * (2048 * 4096 + 4096 * 256 + 2048 * 256)
    assert costs.least_seconds(costs.gemm_flops(2048, k.k, k.n),
                               costs.gemm_bytes(2048, k.k, k.n), peaks.TPU_V5E)[1] == "memory"


def test_whole_model_counts():
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 256 + 3 * 4096 * 13696
    assert dense._matmul_params_per_layer(GLM) == per_layer
    # 28 layers of matmuls, embedding and LM head in bf16, 57 RMSNorm scales in f32
    assert dense.weight_bytes(GLM) == 2 * (28 * per_layer + 2 * 65024 * 4096) + 4 * 57 * 4096
    assert dense.kv_bytes_per_token(GLM) == 28_672
    # one decode token at context 100: matmuls, attention, LM head
    want = 2 * 28 * per_layer + 4 * 28 * 32 * 128 * 100 + 2 * 65024 * 4096
    assert dense.token_flops(GLM, 100, lm_head=True) == want


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v4")
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12


# The dense accounting as it read before it moved into reference/dense.py:
# weight bytes, KV bytes a token, the kernel GEMMs' least seconds (total,
# compute-bound, memory-bound) at M = 32 and 4096, token operations at
# (context, lm_head) = (1, True), (100, True), (2048, False), (7000, True),
# and prompt operations at 1, 384, 3072 and 7680 tokens.
PINNED = {
    "chatglm3-6b": dict(
        weight_bytes=12487376896, kv=28672,
        least={32: (0.014164738188034189, 0.0, 0.014164738188034189),
               4096: (0.2376052088892776, 0.23502409873543148, 0.002581110153846154)},
        token_flops=[11954225152.0, 11999641600.0, 12360613888.0, 15165030400.0],
        prompt_flops=[11954225152.0, 4420142104576.0, 37251488677888.0, 101245410869248.0],
    ),
    "stablelm-12b.pp4": dict(
        weight_bytes=7613521920, kv=51200,
        least={32: (0.006874878437118436, 0.0, 0.006874878437118436),
               4096: (0.1155498815675127, 0.1155498815675127, 0.0)},
        token_flops=[6585262080.0, 6605537280.0, 5976883200.0, 8018657280.0],
        prompt_flops=[6585262080.0, 2150228295680.0, 18040204820480.0, 48722849300480.0],
    ),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_accounting_is_pinned(name):
    cfg, want = _config(name), PINNED[name]
    assert dense.weight_bytes(cfg) == want["weight_bytes"]
    assert dense.kv_bytes_per_token(cfg) == want["kv"]
    for m, (total, comp, mem) in want["least"].items():
        t, by = costs.gemms_least_seconds(dense, cfg, m, peaks.TPU_V5E)
        assert (t, by["compute"], by["memory"]) == (total, comp, mem), m
    ctx = [(1, True), (100, True), (2048, False), (7000, True)]
    assert [dense.token_flops(cfg, c, lm_head=h) for c, h in ctx] == want["token_flops"]
    assert [dense.prompt_flops(cfg, p) for p in (1, 384, 3072, 7680)] == want["prompt_flops"]


def test_least_seconds_sum_per_layer_count():
    """GEMMs that run in different numbers of layers (a leading dense layer
    beside expert layers) each count their own layers."""

    class Family:
        @staticmethod
        def kernel_gemms(cfg):
            return [costs.Gemm("dense_up", 4096, 11008, 0, 1),
                    costs.Gemm("q", 4096, 4096, 0, 27), costs.Gemm("o", 4096, 4096, 1, 27)]

    t, by = costs.gemms_least_seconds(Family, {}, 32, peaks.TPU_V5E)
    one = [costs.least_seconds(costs.gemm_flops(32, k, n), costs.gemm_bytes(32, k, n, e), peaks.TPU_V5E)[0]
           for k, n, e in ((4096, 11008, 0), (4096, 4096, 0), (4096, 4096, 1))]
    assert t == pytest.approx(one[0] + 27 * (one[1] + one[2]))
    assert by == {"compute": 0.0, "memory": pytest.approx(t)}
