"""Operations and bytes of one decode GEMM and one prefill GEMM, counted
by hand."""

import pytest

from lib import costs, peaks

GLM = {"n_layers": 28, "d_model": 4096, "n_heads": 32, "n_kv": 2, "head_dim": 128,
       "d_ff": 13696, "vocab": 65024, "norm": "rmsnorm", "tie_embeddings": False}


def test_decode_gate_gemm_by_hand():
    # gate projection at M=32 slots: [32, 4096] @ [4096, 13696], silu(.)*up
    # in the epilogue reads up [32, 13696]; bf16 throughout.
    g = [x for x in costs.layer_gemms(GLM) if x.name == "gate"][0]
    assert (g.k, g.n, g.extra_mn) == (4096, 13696, 1)
    assert costs.gemm_flops(32, g.k, g.n) == 2 * 32 * 4096 * 13696 == 3_590_324_224
    want = 2 * (32 * 4096 + 4096 * 13696 + 2 * 32 * 13696)
    assert costs.gemm_bytes(32, g.k, g.n, g.extra_mn) == want == 114_212_864
    t, bound = costs.least_seconds(3_590_324_224, want, peaks.TPU_V5E)
    assert bound == "memory" and t == pytest.approx(want / 819e9)


def test_prefill_q_gemm_by_hand():
    # Q projection of a 2048-token prompt: [2048, 4096] @ [4096, 4096].
    g = [x for x in costs.layer_gemms(GLM) if x.name == "q"][0]
    flops = 2 * 2048 * 4096 * 4096
    assert costs.gemm_flops(2048, g.k, g.n) == flops == 68_719_476_736
    nbytes = 2 * (2048 * 4096 + 4096 * 4096 + 2048 * 4096)
    assert costs.gemm_bytes(2048, g.k, g.n) == nbytes == 67_108_864
    t, bound = costs.least_seconds(flops, nbytes, peaks.TPU_V5E)
    assert bound == "compute" and t == pytest.approx(flops / 197e12)
    # the K projection of the same prompt (N = 256) is bound by memory
    k = [x for x in costs.layer_gemms(GLM) if x.name == "k"][0]
    nbytes = 2 * (2048 * 4096 + 4096 * 256 + 2048 * 256)
    assert costs.least_seconds(costs.gemm_flops(2048, k.k, k.n),
                               costs.gemm_bytes(2048, k.k, k.n), peaks.TPU_V5E)[1] == "memory"


def test_whole_model_counts():
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 256 + 3 * 4096 * 13696
    assert costs.matmul_params_per_layer(GLM) == per_layer
    # 28 layers of matmuls, embedding and LM head in bf16, 57 RMSNorm scales in f32
    assert costs.weight_bytes(GLM) == 2 * (28 * per_layer + 2 * 65024 * 4096) + 4 * 57 * 4096
    assert costs.kv_bytes_per_token(GLM) == 28_672
    # one decode token at context 100: matmuls, attention, LM head
    want = 2 * 28 * per_layer + 4 * 28 * 32 * 128 * 100 + 2 * 65024 * 4096
    assert costs.token_flops(GLM, 100, lm_head=True) == want


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v4")
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
