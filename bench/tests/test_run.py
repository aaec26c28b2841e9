"""Whole runs of a reduced cell on the CPU, kernels in interpret mode:
the shape of the result line, the output check against its precision
control and against a planted fault, and a cell made only of new files."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, run_cell, write_json


def _units(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    return {m["name"]: m["unit"] for m in b["end_to_end"] + b["per_layer"]}


def test_result_line_and_precision_control(tiny_root, interpret):
    root, name = tiny_root("chatglm3-6b")
    out = run_cell(root, name)
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    units = _units(root)
    assert {"setup_s", "output_tok_s", "itl_p50_ms", "itl_p95_ms"} == set(out["metrics"])
    for k, m in out["metrics"].items():
        assert m["unit"] == units[k] and m["value"] > 0
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    json.dumps(out)
    # the float8 reference in the program's place, through the same comparison
    ctl = run_cell(root, name, control=True)
    assert ctl["correct"] is False
    c = ctl["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"] == gap["limit"]


def test_traced_line(tiny_root, interpret):
    root, name = tiny_root("stablelm-12b.pp4")
    out = run_cell(root, name, seconds=2.5, trace=True)
    assert out["correct"] is True
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10 and len(out["breakdown"]["idle_gaps"]) <= 10
    for k in ("tokens_per_step", "decode_step_ms", "device_idle_share", "prefill_ms_per_ktok", "step_mfu"):
        assert out["metrics"][k]["value"] > 0, k
    assert "output_tok_s" not in out["metrics"]


def test_altered_tokens_are_not_correct(tiny_root, interpret, monkeypatch):
    """A token altered where it is produced: the decode step's sampler
    returns the runner-up's neighbour instead of the argmax."""
    from repro.serve import continuous

    real = continuous.sample_token

    def altered(logits, key, temperature=0.0):
        return (real(logits, key, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(continuous, "sample_token", altered)
    root, name = tiny_root("chatglm3-6b")
    out = run_cell(root, name, seed=11)
    assert out["correct"] is False
    c = out["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]


FAMILY = '''"""A family of its own name: the dense family's shapes, costs and
reference, re-exported, with a record of what the harness asked of it."""

from reference import dense

CALLS = []


def _logged(name):
    def call(*a, **k):
        CALLS.append(name)
        return getattr(dense, name)(*a, **k)
    return call


for _n in ("served", "refuses", "layout", "kernel_gemms", "token_flops", "prompt_flops",
           "weight_bytes", "kv_bytes_per_token", "logits_at"):
    globals()[_n] = _logged(_n)
'''


def _files(root):
    out = {}
    for d, _, names in os.walk(root):
        for n in names:
            if "__pycache__" not in d:
                p = os.path.join(d, n)
                out[os.path.relpath(p, root)] = open(p, "rb").read()
    return out


def test_a_cell_of_new_files_runs(tiny_root, interpret):
    """A configuration of a family of its own, a mix, a cell and an
    end-to-end metric added as files of their own and entries in
    BENCHMARK.json, with no edit to any file the benchmark had."""
    import time

    from lib import harness, peaks, spec

    root, _ = tiny_root("stablelm-12b.pp4")
    before = _files(os.path.join(root, "bench"))
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(root, "bench/configs/tiny-stablelm-12b.pp4.json")))
    cfg["name"] = "growth"
    cfg["reference"] = "growth_family"
    with open(os.path.join(root, "bench/reference/growth_family.py"), "w") as f:
        f.write(FAMILY)
    write_json(os.path.join(root, "bench/configs/growth.json"), cfg)
    write_json(os.path.join(root, "bench/traffic/shortmix.json"), {
        "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.3, "min": 6, "max": 30},
        "output": {"dist": "lognormal", "median": 6, "sigma": 0.3, "min": 3, "max": 10},
        "stream": True})
    cell = json.load(open(os.path.join(root, "bench/cells/tiny-stablelm-12b.pp4.chat.json")))
    write_json(os.path.join(root, "bench/cells/growth.short.json"), dict(cell, slots=2, join_rows=[1, 2]))
    with open(os.path.join(root, "bench/metrics/requests_per_s.py"), "w") as f:
        f.write("def read(run):\n"
                "    n = sum(1 for ts in run.times.values() if run.t_open <= ts[0] < run.t_close)\n"
                "    return n / (run.t_close - run.t_open)\n")
    b["configs"].append({"name": "growth", "source": "test", "file": "bench/configs/growth.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "growth.short", "config": "growth", "traffic": "shortmix", "chips": 1, "why": "t"})
    b["end_to_end"].append({"name": "requests_per_s", "unit": "requests/s", "better": "higher",
                            "bound": 0.05, "source": "host_clock", "workloads": ["growth.short"]})
    b["per_layer"].append({"name": "step_mfu", "unit": "%", "better": "higher", "source": "device_trace",
                           "layer": "whole step", "moves": "requests_per_s", "workloads": ["growth.short"]})
    b["per_layer"] = [m for m in b["per_layer"] if m["name"] != "step_mfu" or "growth.short" in m["workloads"]]
    write_json(os.path.join(root, "BENCHMARK.json"), b)
    after = _files(os.path.join(root, "bench"))
    assert {k: after[k] for k in before} == before
    out = run_cell(root, "growth.short")
    assert out["correct"] is True
    assert out["metrics"]["requests_per_s"]["value"] > 0
    assert "itl_p95_ms" not in out["metrics"]
    # the harness and the readers asked the new family, not the dense module
    c = spec.load_cell(root, "growth.short")
    traced = harness.run_once(c, 8, 2.5, True, t_start=time.perf_counter(), peaks=peaks.TPU_V5E,
                              backend="pallas_interpret")
    assert traced["correct"] is True
    assert traced["metrics"]["step_mfu"]["value"] > 0
    # (kernel_gemms is read only where a trace has opope_gemm time: on the
    # chip, and in test_trace's reader tests)
    assert {"served", "refuses", "layout", "token_flops", "prompt_flops",
            "weight_bytes", "kv_bytes_per_token", "logits_at"} <= set(c.family.CALLS)


@pytest.mark.parametrize("change", ["moe", "window", "attn_softcap"])
def test_a_family_refuses_what_it_lacks(tiny_root, change):
    """The dense family refuses a configuration with a mechanism its
    reference lacks, whatever else the file states."""
    from repro.configs.base import MoESpec

    from lib import harness, spec

    root, name = tiny_root("chatglm3-6b")
    cell = spec.load_cell(root, name)
    cell.config["changes"][change] = {"moe": MoESpec(n_experts=4, top_k=2, d_ff_expert=96),
                                      "window": 16, "attn_softcap": 50.0}[change]
    with pytest.raises(ValueError, match="^tiny-chatglm3-6b: mechanisms the dense reference lacks$"):
        harness.served_config(cell)


def test_longest_gap_names_what_the_host_did():
    from lib import harness

    c = harness.Client({1: 10, 2: 20, 3: 30}, n_slots=2, seconds=10.0)
    c.t_open, c.t_close = 100.0, 110.0
    c.times = {1: [99.0, 100.5, 101.0, 103.0, 103.1], 2: [100.2, 100.6], 3: [102.0, 102.1]}
    line = harness.longest_gap(c, [(102.5, "compile", 0.1)], [(101.5, 102.2, 2), (104.0, 104.5, 0)])
    assert line.startswith("2000.000 ms (request 1, 1.000 s to 3.000 s into the window)")
    assert "first tokens of other requests inside it: 1 (prompt lengths [30])" in line
    assert "garbage collections inside it: 1 (0.7000 s, generations [2])" in line
    assert line.endswith("programs compiled or loaded inside it: 1")


@pytest.mark.parametrize("alone", [False, True], ids=["checkout", "bench-only"])
def test_no_tpu_no_result(tmp_path, alone):
    cwd = ROOT
    if alone:
        import shutil

        shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
        cwd = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "chatglm3-6b.chat", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr
