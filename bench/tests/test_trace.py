"""The trace reduction, checked by hand on a small record: two decode
executions, one prefill, a GEMM kernel and attention ops, host join marks.

Times are in ns on the trace's clock; the window is [1000, 11000]."""

import pytest

from conftest import reader
from lib import trace

REC = {
    "modules": [
        [1500, 2000, "jit__decode(7)"],
        [4000, 3000, "jit__prefill(9)"],
        [7500, 2000, "jit__decode(7)"],
        [10500, 1500, "jit__decode(7)"],  # runs past the window's end
    ],
    "ops": [
        [1500, 800, "opope_gemm.3", "jit__decode", ""],
        [2300, 500, "fusion.12", "jit__decode", "attn_core"],
        [2800, 700, "fusion.13", "jit__decode", "norm"],
        [4000, 2500, "opope_gemm.9", "jit__prefill", ""],
        [6500, 500, "fusion.40", "jit__prefill", "attn_core"],
        [7500, 1000, "opope_gemm.3", "jit__decode", ""],
        [8500, 1000, "fusion.12", "jit__decode", "attn_core"],
        [10500, 1000, "opope_gemm.3", "jit__decode", ""],
        [600, 300, "copy.1", "", ""],  # before the window
    ],
    "host": [
        [1000, 0, "bench.trace_start", "python", {}],
        [7100, 0, "bench.join", "python", {"rid": 4, "plen": 300}],
        [7200, 0, "bench.join", "python", {"rid": 5, "plen": 200}],
        [3600, 300, "PjitFunction(_prefill)", "python", {}],
        [9500, 800, "TransferToHost", "python", {}],
        [11000, 0, "bench.trace_end", "python", {}],
    ],
}


@pytest.fixture
def red():
    return trace.Reduced(REC)


def test_busy_and_idle(red):
    # busy: [1500,3500] + [4000,7000] + [7500,9500] + [10500,11000] (clipped)
    assert red.window_s == pytest.approx(10000e-9)
    assert red.busy_s == pytest.approx(7500e-9)
    idle = 1 - red.busy_s / red.window_s
    assert idle == pytest.approx(0.25)
    # gaps: [1000,1500] 500, [3500,4000] 500, [7000,7500] 500, [9500,10500] 1000
    gaps = red.idle_gaps()
    assert gaps[0] == ["TransferToHost", pytest.approx(1000e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx([500e-9] * 3 + [1000e-9])
    assert ["PjitFunction(_prefill)", pytest.approx(500e-9)] in gaps


def test_executions_and_scopes(red):
    # only whole executions inside the window count
    assert red.count == {"decode": 2, "prefill": 1}
    assert red.module_s["decode"] == pytest.approx(4000e-9)
    assert red.gemm_s == {"decode": pytest.approx(1800e-9), "prefill": pytest.approx(2500e-9)}
    assert red.scope_s[("decode", "attn_core")] == pytest.approx(1500e-9)
    assert red.scope_s[("prefill", "attn_core")] == pytest.approx(500e-9)
    assert red.scope_s[("decode", "norm")] == pytest.approx(700e-9)


def test_prefill_tokens_follow_their_execution(red):
    assert red.prefills() == [(pytest.approx(3000e-9), 500, pytest.approx(2500e-9))]


def test_top_ops_have_stable_names(red):
    top = dict(red.top_ops())
    assert top["prefill/opope_gemm"] == pytest.approx(2500e-9)
    assert top["decode/attn_core/fusion"] == pytest.approx(1500e-9)
    assert top["decode/opope_gemm"] == pytest.approx(1800e-9)


def test_scope_names_match_whole_words():
    assert trace._scopes("fusion.1", {"tf_op": "jit(_decode)/while/body/attn_core/dot_general"}) == "attn_core"
    assert trace._scopes("fusion.1", {"tf_op": "jit(_decode)/while/body/normal/x"}) == ""


def test_recorded_tpu_excerpt():
    """The first 400 operations of one decode step of chatglm3-6b.rag, as a
    TPU v5e trace recorded them (each named by its HLO text, the layer loop
    a ``while`` that spans its body), with the execution cut to the
    excerpt."""
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "data", "tpu_decode_excerpt.json")) as f:
        rec = json.load(f)
    red = trace.Reduced(rec)
    ops = rec["ops"]
    gemm = sum(o[1] for o in ops if trace.op_name(o[2]).startswith("opope_gemm")) * 1e-9
    assert sum(1 for o in ops if "opope_gemm" in o[2]) == 64
    assert red.count == {"decode": 1}
    assert red.gemm_s["decode"] == pytest.approx(gemm)
    # the while loop holds the rest: it counts towards busy time, not as an op
    assert 0 < red.busy_s <= red.window_s
    names = [k for k, _ in red.top_ops(50)]
    assert "decode/opope_gemm" in names and not any("while" in n or " = " in n for n in names)
    assert trace.op_name(ops[0][2]) == "fusion.61"


def test_gemm_roofline_reads_the_family(red):
    """The roofline reader takes its GEMMs from the run's family module."""
    import types

    from lib import costs, peaks

    asked = []

    class Family:
        @staticmethod
        def kernel_gemms(cfg):
            asked.append(cfg)
            return [costs.Gemm("g", 64, 64, 0, 2)]

    run = types.SimpleNamespace(trace=red, family=Family, cfg={"tag": 1}, peaks=peaks.TPU_V5E)
    # 500 prompt tokens: [500, 64] @ [64, 64] in 2 layers, bound by memory,
    # against 2500 ns of opope_gemm time in the prefill
    want = 2 * 2 * (500 * 64 + 64 * 64 + 500 * 64) / 819e9 / 2500e-9 * 100
    assert reader("gemm_prefill_roofline")(run) == pytest.approx(want)
    assert asked == [{"tag": 1}]
