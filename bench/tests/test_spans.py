"""The engine-span readers, checked by hand on a small record: two decode
executions inside the window and one that runs past its end, one prefill,
the engine's spans around them (one starting before the window, one running
past its end, one nested pair) and a runtime event.

Times are in ns on the trace's clock; the window is [1000, 11000]. Device
busy: [1500, 3500], [4000, 7000], [7500, 9500], [10500, 10800]. Idle:
[1000, 1500], [3500, 4000], [7000, 7500], [9500, 10500], [10800, 11000]."""

import types

import pytest

from conftest import reader
from lib import spans, trace

REC = {
    "modules": [
        [1500, 2000, "jit_engine_decode(7)"],
        [4000, 3000, "jit_engine_prefill(9)"],
        [7500, 2000, "jit_engine_decode(7)"],
        [10500, 1500, "jit_engine_decode(7)"],  # runs past the window's end
    ],
    "ops": [
        [1500, 2000, "opope_gemm.3", "jit_engine_decode", ""],
        [4000, 3000, "opope_gemm.9", "jit_engine_prefill", ""],
        [7500, 2000, "opope_gemm.3", "jit_engine_decode", ""],
        [10500, 300, "opope_gemm.3", "jit_engine_decode", ""],
    ],
    "host": [
        [1000, 0, "bench.trace_start", "python", {}],
        [800, 100, "serve.prefill", "python", {}],  # before the window
        [900, 300, "serve.telemetry", "python", {}],  # idle 200 inside
        [1300, 200, "serve.decode", "python", {}],  # idle 200
        [3000, 700, "serve.readback", "python", {}],  # idle 200
        [3700, 100, "serve.emit", "python", {}],  # idle 100
        [3800, 100, "serve.admit", "python", {}],  # idle 100
        [3900, 200, "serve.prefill", "python", {}],  # idle 100
        [6800, 600, "serve.prefill", "python", {}],  # idle 400
        [7100, 100, "serve.admit", "python", {}],  # nested: counts once
        [9000, 700, "np.asarray_jax.Array_", "python", {}],
        [9000, 600, "serve.readback", "python", {}],  # idle 100
        [9600, 300, "serve.emit", "python", {}],  # idle 300
        [9900, 100, "serve.telemetry", "python", {}],  # idle 100
        [10300, 300, "serve.decode", "python", {}],  # idle 200
        [10900, 500, "serve.readback", "python", {}],  # idle 100, past the end
        [11000, 0, "bench.trace_end", "python", {}],
    ],
}


@pytest.fixture
def red():
    return trace.Reduced(REC)


def test_idle_intervals(red):
    assert spans.idle_intervals(red) == [
        (1000, 1500), (3500, 4000), (7000, 7500), (9500, 10500), (10800, 11000)]


def test_idle_inside_spans(red):
    # step spans: 200 + 200 + 200 + 100 + 100 + 300 + 100 + 200 + 100
    assert spans.idle_inside_s(red, spans.STEP) == pytest.approx(1500e-9)
    # join spans: 100 + 100 + 400 (the nested admit adds nothing)
    assert spans.idle_inside_s(red, spans.JOIN) == pytest.approx(600e-9)
    assert spans.idle_inside_s(red, ["serve.chunk"]) is None
    assert spans.starts(red, "serve.prefill") == 2


def test_readers(red):
    run = types.SimpleNamespace(trace=red)
    assert red.count["decode"] == 2
    assert reader("host_ms_per_step")(run) == pytest.approx(1500e-9 / 2 * 1e3)
    assert reader("host_ms_per_join")(run) == pytest.approx(600e-9 / 2 * 1e3)


def test_readers_find_nothing_without_engine_spans():
    rec = dict(REC, host=[h for h in REC["host"] if not h[2].startswith("serve.")])
    run = types.SimpleNamespace(trace=trace.Reduced(rec))
    assert reader("host_ms_per_step")(run) is None
    assert reader("host_ms_per_join")(run) is None


def test_idle_gaps_are_named_by_engine_spans(red):
    gaps = dict((round(d * 1e9), n) for n, d in red.idle_gaps())
    # [9500, 10500]: serve.emit overlaps it by 300, the runtime event by 200
    assert gaps[1000] == "serve.emit"


def test_overlap_of_interval_lists():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (28, 45)]
    assert spans.overlap_ns(a, b) == 5 + 5 + 2 + 5
    assert spans.overlap_ns(a, []) == 0
