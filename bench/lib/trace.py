"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Two steps. ``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
into a compact, JSON-able record: the device's operations and program
executions, and the host's events. ``Reduced`` turns that record and the
traced window into sums: busy time (the union of operation intervals),
program executions by kind, the time of GEMM kernels and of named scopes
inside them, the device operations that took most time and the longest
idle gaps with what the host was doing in them.

Program kinds come from the jitted functions' names as the serving engine
defines them (``_decode``, ``_prefill``, ``_chunk``); GEMM kernels from the
Mosaic kernel's name (``opope_gemm``); scopes from the ``jax.named_scope``
names that reach the operations' metadata (``attn_core`` and others).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

KINDS = (("decode", "_decode"), ("prefill", "_prefill"), ("chunk", "_chunk"))
SCOPES = ("attn_core", "opope_epilogue", "norm", "rope")
GEMM_KERNEL = "opope_gemm"
MARK_START, MARK_END, MARK_JOIN = "bench.trace_start", "bench.trace_end", "bench.join"


CONTAINERS = ("while", "conditional", "call")  # ops that hold other ops


def op_name(text: str) -> str:
    """The HLO instruction's name: a TPU trace names an operation by its
    whole HLO text (``%fusion.61 = s32[32]... fusion(...)``)."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the known scopes in its ``op_name`` metadata
    (empty where none), for every instruction of a compiled program's HLO
    text."""
    out = {}
    inst = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
    meta = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
    for line in hlo_text.splitlines():
        m = inst.match(line)
        if m:
            md = meta.search(line)
            out[m.group(1)] = _scopes("", {"op_name": md.group(1)}) if md else ""
    return out


def module_kind(name: str) -> Optional[str]:
    for kind, token in KINDS:
        if token in name:
            return kind
    return None


def _scopes(name: str, stats: Dict) -> str:
    text = " ".join([name] + [v for v in stats.values() if isinstance(v, str)])
    return ",".join(s for s in SCOPES if re.search(rf"(^|[/ (]){s}([/ ).]|$)", text))


def _stats(e) -> Dict:
    out = {}
    for k, v in e.stats:
        out[k] = v if isinstance(v, (int, float)) else str(v)
    return out


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict:
    """Compact record of one trace: ``ops`` [start_ns, dur_ns, hlo op,
    module, scopes], ``modules`` [start_ns, dur_ns, name] and ``host``
    [start_ns, dur_ns, name, thread, stats]. Device operations are those of
    the accelerator's planes; where a backend reports none (the CPU), the
    operations that carry an ``hlo_module`` stat stand in, and program
    executions are made from them."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host, cpu_ops = [], [], [], []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            for e in line.events:
                st = _stats(e)
                if is_dev:
                    if line.name == "XLA Modules":
                        modules.append([e.start_ns, e.duration_ns, e.name])
                    elif line.name == "XLA Ops":
                        ops.append([e.start_ns, e.duration_ns, e.name,
                                    str(st.get("hlo_module", "")), _scopes("", st)])
                elif plane.name.startswith("/host:"):
                    if "hlo_module" in st and e.duration_ns > 0:
                        cpu_ops.append([e.start_ns, e.duration_ns, str(st.get("hlo_op", e.name)),
                                        str(st["hlo_module"]), _scopes("", st),
                                        st.get("run_id", 0)])
                    elif e.duration_ns > 0 or e.name.startswith("bench."):
                        keep = {k: v for k, v in st.items() if k in ("rid", "plen")}
                        host.append([e.start_ns, e.duration_ns, e.name, line.name, keep])
    if not ops and cpu_ops:
        runs: Dict[Tuple, List] = {}
        for s, d, name, mod, sc, run in cpu_ops:
            ops.append([s, d, name, mod, sc])
            r = runs.setdefault((mod, run), [s, s + d])
            r[0], r[1] = min(r[0], s), max(r[1], s + d)
        modules = [[a, b - a, mod] for (mod, _), (a, b) in runs.items()]
    ops.sort()
    modules.sort()
    host.sort(key=lambda h: h[0])
    return {"ops": ops, "modules": modules, "host": host}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def stable_op_name(kind: Optional[str], name: str, scopes: str) -> str:
    name = op_name(name)
    base = GEMM_KERNEL if name.startswith(GEMM_KERNEL) else re.sub(r"(\.\d+)+(\.remat\d*)?$", "", name)
    where = scopes.split(",")[0] if scopes else ""
    parts = [kind or "other", where, base]
    return "/".join(p for p in parts if p)


class Reduced:
    """Sums over the traced window ``[t0, t1]`` (trace clock, ns)."""

    def __init__(self, rec: Dict, scopes: Optional[Dict[str, Dict[str, str]]] = None):
        """``scopes``: module kind -> instruction name -> scopes, for
        operations whose trace event carries no scope of its own."""
        scopes_map = scopes or {}
        host = rec["host"]
        marks = {h[2]: h[0] for h in host if h[2] in (MARK_START, MARK_END)}
        ops, mods = rec["ops"], rec["modules"]
        self.t0 = marks.get(MARK_START, min((o[0] for o in ops), default=0))
        self.t1 = marks.get(MARK_END, max((o[0] + o[1] for o in ops), default=0))
        t0, t1 = self.t0, self.t1
        self.window_s = (t1 - t0) * 1e-9
        clipped = [(max(o[0], t0), min(o[0] + o[1], t1)) for o in ops]
        self.busy_iv = union([c for c in clipped if c[1] > c[0]])
        self.busy_s = sum(b - a for a, b in self.busy_iv) * 1e-9
        # Whole program executions inside the window, by kind.
        self.modules = [m for m in mods if m[0] >= t0 and m[0] + m[1] <= t1]
        self.count: Dict[str, int] = {}
        self.module_s: Dict[str, float] = {}
        for s, d, name in self.modules:
            k = module_kind(name) or "other"
            self.count[k] = self.count.get(k, 0) + 1
            self.module_s[k] = self.module_s.get(k, 0.0) + d * 1e-9
        # Operations attributed to the execution that holds their start.
        starts = [m[0] for m in self.modules]
        self.gemm_s: Dict[str, float] = {}
        self.gemm_by_module: Dict[int, float] = {}
        self.scope_s: Dict[Tuple[str, str], float] = {}
        self.op_s: Dict[str, float] = {}
        self.mapped = self.unmapped = 0
        for s, d, name, _mod, scopes in ops:
            if s < t0 or s + d > t1:
                continue
            i = bisect.bisect_right(starts, s) - 1
            kind = None
            if i >= 0 and s < self.modules[i][0] + self.modules[i][1]:
                kind = module_kind(self.modules[i][2]) or "other"
            base = op_name(name)
            if base.split(".")[0] in CONTAINERS:
                continue
            if not scopes and scopes_map and kind in scopes_map:
                table = scopes_map[kind]
                if base in table:
                    scopes = table[base]
                    self.mapped += 1
                else:
                    self.unmapped += 1
            if kind is not None:
                if base.startswith(GEMM_KERNEL):
                    self.gemm_s[kind] = self.gemm_s.get(kind, 0.0) + d * 1e-9
                    self.gemm_by_module[i] = self.gemm_by_module.get(i, 0.0) + d * 1e-9
                for sc in filter(None, scopes.split(",")):
                    self.scope_s[(kind, sc)] = self.scope_s.get((kind, sc), 0.0) + d * 1e-9
            key = stable_op_name(kind, name, scopes)
            self.op_s[key] = self.op_s.get(key, 0.0) + d * 1e-9
        self.joins = [h for h in host if h[2] == MARK_JOIN and t0 <= h[0] <= t1]
        self.host = [h for h in host if not h[2].startswith("bench.") and h[1] > 0]

    def prefills(self) -> List[Tuple[float, int, float]]:
        """(device seconds, unpadded prompt tokens, GEMM kernel seconds) of
        each whole prefill execution in the window. Each join mark (made
        when the request's first token reached the host) belongs to the last
        prefill that ended before it; a prefill that no mark follows is left
        out."""
        pf = sorted(
            (m[0] + m[1], m[1], i) for i, m in enumerate(self.modules)
            if module_kind(m[2]) == "prefill"
        )
        ends = [e for e, _, _ in pf]
        tokens = [0] * len(pf)
        for h in self.joins:
            j = bisect.bisect_right(ends, h[0]) - 1
            if j >= 0:
                tokens[j] += int(h[4].get("plen", 0))
        return [
            (d * 1e-9, n, self.gemm_by_module.get(i, 0.0))
            for (_, d, i), n in zip(pf, tokens) if n > 0
        ]

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest intervals with no device operation, each named by
        the host event that overlaps it most (``idle`` where none does)."""
        gaps, prev = [], self.t0
        for a, b in self.busy_iv:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if self.t1 > prev:
            gaps.append((prev, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            best, name = 0, "idle"
            for s, d, hname, _thread, _st in self.host:
                ov = min(b, s + d) - max(a, s)
                if ov > best:
                    best, name = ov, hname
            out.append([name, (b - a) * 1e-9])
        return out
