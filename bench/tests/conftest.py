"""CPU tests of the benchmark: run with ``python -m pytest bench/tests``
from the checkout's root (``JAX_PLATFORMS=cpu``).

``tiny_root`` builds a throwaway checkout: a copy of ``bench/``, the
program's ``src`` linked in, and a ``BENCHMARK.json`` with one extra cell
of a reduced chatglm3-6b or stablelm-12b.pp4 (2 layers, d_model 64) under
a short mix. Runs go through ``lib.harness.run_once`` with the Pallas
kernels in interpret mode; only the look for a TPU is skipped.
"""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv=2, head_dim=16, d_ff=96, vocab=256)
TINY_MIX = {
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 56},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4, "max": 16},
    "stream": True,
}
TINY_CELL = {
    "slots": 4, "max_len": 128, "prefill_chunk": None, "prefix_cache": False,
    "kv_format": None, "backlog": 400, "join_rows": [1, 2, 4],
    "trace_lead_s": 0.3, "trace_seconds": 1.0, "check_tokens": 32,
    "max_logit_gap": 0.05,
}


def tiny_config(base: str, **extra):
    with open(os.path.join(BENCH, "configs", base + ".json")) as f:
        cfg = json.load(f)
    ch = dict(TINY, **extra)
    cfg["changes"] = dict(cfg["changes"], **ch)
    cfg["config"].update(ch)
    return cfg


def write_json(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def tiny_root(tmp_path):
    """``make(base, cell=..., mix=..., config=...)`` -> (root, cell name)."""

    def make(base="chatglm3-6b", cell=None, mix=None, **extra):
        root = tmp_path / "checkout"
        shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
        os.symlink(os.path.join(ROOT, "src"), root / "src")
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            b = json.load(f)
        name = "tiny-" + base
        b["configs"].append({"name": name, "source": "test", "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
        b["workloads"].append({"name": name + ".chat", "config": name, "traffic": "tinymix", "chips": 1, "why": "test"})
        for m in b["end_to_end"] + b["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(name + ".chat")
        write_json(str(root / "BENCHMARK.json"), b)
        cfg = tiny_config(base, **extra)
        cfg["name"] = name
        write_json(str(root / "bench" / "configs" / f"{name}.json"), cfg)
        write_json(str(root / "bench" / "traffic" / "tinymix.json"), mix or TINY_MIX)
        write_json(str(root / "bench" / "cells" / f"{name}.chat.json"), dict(TINY_CELL, **(cell or {})))
        return str(root), name + ".chat"

    return make


@pytest.fixture
def interpret():
    """Serve through the Pallas kernels in interpret mode."""
    from repro.kernels import ops

    prev = ops._DEFAULT_BACKEND
    ops.set_default_backend("pallas_interpret")
    yield "pallas_interpret"
    ops.set_default_backend(prev)


def reader(name):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    import importlib.util

    s = importlib.util.spec_from_file_location(
        "bench_metric_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def run_cell(root, name, seed=7, seconds=2.0, trace=False, backend="pallas_interpret", **kw):
    import time

    from lib import harness, peaks, spec

    cell = spec.load_cell(root, name)
    return harness.run_once(cell, seed, seconds, trace, t_start=time.perf_counter(),
                            peaks=peaks.TPU_V5E, backend=backend, **kw)
