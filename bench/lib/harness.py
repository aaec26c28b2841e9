"""One run of one cell: set-up, a measured window, the output check, and
the result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights from the seed (one jitted call, on the device, in
bf16), builds the serving engine the cell's deployment describes, warms up
every prefill and join shape the cell's traffic can use, and starts the
backlog. The first ``slots`` requests arrive one engine tick apart, so the
pool fills one request at a time; the window opens when the last of them
has streamed its first token, and closes ``--seconds`` later from inside
the client's ``on_token`` callback, whether or not the backlog is drained.

The end-to-end metrics are taken on the host clock from what the callback
saw. With ``--trace 1`` a profiler trace of a steady part of the window
feeds the per-layer metrics instead, one reader per metric under
``bench/metrics/``.

After the window the program's state is freed, and a sample of the requests
that finished is run through the float32 reference: the widest gap by which
a served (greedy) token's reference logit lies below the reference's best,
against the cell's limit, decides ``correct``.

Exit codes: 0 with a result line; 2 for a malformed cell; 3 where JAX finds
no TPU or fewer chips than the cell asks for; 4 where the run is not the
system under test (a compile inside the window, or a serving GEMM on a
backend other than the compiled ``pallas``). Only exit 0 prints a result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from . import spec, traffic
from .trace import MARK_END, MARK_JOIN, MARK_START

COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


# (host clock, event, seconds) of every program compiled or loaded
COMPILES: List[tuple] = []
_LISTENING = []


def _listen() -> None:
    if not _LISTENING:
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            lambda ev, secs, **_: COMPILES.append((time.perf_counter(), ev, secs))
            if ev in COMPILE_EVENTS else None
        )
        _LISTENING.append(True)


def _compile_summary(events) -> str:
    by = {}
    for _, ev, secs in events:
        n, t = by.get(ev.rsplit("/", 1)[-1], (0, 0.0))
        by[ev.rsplit("/", 1)[-1]] = (n + 1, t + secs)
    return ", ".join(f"{k} {n} in {t:.3f}s" for k, (n, t) in sorted(by.items())) or "none"


class GcLog:
    """Start, end and generation of every garbage collection while it is
    in ``gc.callbacks``: host context for the window's longest gap."""

    def __init__(self):
        self.spans: List[tuple] = []
        self._t0 = None

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.spans.append((self._t0, time.perf_counter(), info.get("generation")))
            self._t0 = None


class WindowClosed(Exception):
    """Raised from the callback to end the serve once the window closes."""


class InvalidRun(Exception):
    """The run did not measure the system under test."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# the client
# --------------------------------------------------------------------------


class Client:
    """The ``on_token`` callback: stamps every streamed token, opens and
    closes the window, and starts and stops the profiler inside it."""

    def __init__(self, plen: Dict[int, int], n_slots: int, seconds: float,
                 trace: Optional[Dict] = None):
        self.plen = plen
        self.n_slots = n_slots
        self.seconds = seconds
        self.trace = trace  # {"dir", "lead", "seconds"} or None
        self.tokens: Dict[int, List[int]] = {}
        self.times: Dict[int, List[float]] = {}
        self.n_joined = 0
        self.t_open = self.t_close = None
        self.trace_t0 = self.trace_t1 = None
        self.callback_s = 0.0

    def __call__(self, rid: int, token: int) -> None:
        now = time.perf_counter()
        toks = self.tokens.setdefault(rid, [])
        toks.append(int(token))
        self.times.setdefault(rid, []).append(now)
        if len(toks) == 1:
            self.n_joined += 1
            if self.trace_t0 is not None and self.trace_t1 is None:
                import jax

                with jax.profiler.TraceAnnotation(MARK_JOIN, rid=rid, plen=self.plen[rid]):
                    pass
            if self.t_open is None and self.n_joined == self.n_slots:
                self.t_open, self.t_close = now, now + self.seconds
        if self.t_open is not None:
            if self.trace is not None:
                self._profile(now)
            if now >= self.t_close:
                raise WindowClosed
        self.callback_s += time.perf_counter() - now

    def _profile(self, now: float) -> None:
        import jax

        tr = self.trace
        if self.trace_t0 is None and now >= self.t_open + tr["lead"]:
            # The runtime's own host events, without Python function events:
            # those would slow the host loop that the trace measures.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tr["dir"], profiler_options=opts)
            with jax.profiler.TraceAnnotation(MARK_START):
                pass
            self.trace_t0 = time.perf_counter()
        elif self.trace_t1 is None and self.trace_t0 is not None and (
            now >= self.trace_t0 + tr["seconds"]
        ):
            self.trace_t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation(MARK_END):
                pass
            jax.profiler.stop_trace()


@dataclasses.dataclass
class RunData:
    """What the metric readers read."""

    cfg: Dict
    family: object  # the configuration's module under bench/reference/
    peaks: object
    n_slots: int
    setup_s: float
    t_open: float
    t_close: float
    times: Dict[int, List[float]]
    plen: Dict[int, int]
    trace: object = None  # lib.trace.Reduced
    trace_t0: Optional[float] = None
    trace_t1: Optional[float] = None

    def tokens_between(self, t0: float, t1: float) -> int:
        return sum(sum(1 for t in ts if t0 <= t < t1) for ts in self.times.values())

    def decode_tokens_between(self, t0: float, t1: float) -> int:
        return sum(sum(1 for t in ts[1:] if t0 <= t < t1) for ts in self.times.values())

    def gaps(self) -> np.ndarray:
        out = []
        for ts in self.times.values():
            a = np.asarray(ts)
            ok = (a[:-1] >= self.t_open) & (a[1:] < self.t_close)
            out.append(np.diff(a)[ok])
        return np.concatenate(out) if out else np.zeros(0)


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------


def served_config(cell: spec.Cell):
    """The program's ArchConfig for the cell, checked key by key against
    the configuration file by the configuration's family module."""
    from repro.configs import get_config

    c = cell.config
    cfg = dataclasses.replace(get_config(c["arch"]), **c.get("changes", {}))
    for k, v in cell.family.served(cfg).items():
        if k not in c["config"]:
            raise ValueError(f"{c['name']}: configuration file lacks {k!r}")
        if c["config"][k] != v:
            raise ValueError(
                f"{c['name']}: the program would run {k}={v!r}, "
                f"the file states {c['config'][k]!r}"
            )
    why = cell.family.refuses(cfg)
    if why:
        raise ValueError(f"{c['name']}: {why}")
    return cfg


def check_layout(cfg, params) -> None:
    """The seed's weights have the program's parameter tree, leaf by leaf."""
    import functools

    import jax
    from repro.models import api

    want = jax.eval_shape(functools.partial(api.init_params, cfg), jax.random.key(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if jax.tree.structure(got) != jax.tree.structure(want) or jax.tree.leaves(got) != jax.tree.leaves(want):
        raise ValueError(f"weight layout differs from the program's: {got} vs {want}")


def warmup_requests(Request, sched, buckets: List[int], rows: List[int], vocab: int):
    """Groups that arrive one tick apart and each join as one batch: every
    (rows, bucket) prefill and scatter, and every batch size up to the
    largest row count at the smallest bucket."""
    rng = np.random.default_rng(0)
    groups = [(b, r) for b in buckets for r in rows]
    groups += [(buckets[0], n) for n in range(1, max(rows) + 1) if n not in rows]
    reqs, rid = [], 0
    for tick, (b, n) in enumerate(groups):
        plen = b - 2
        if sched.bucket(plen) != b:
            raise ValueError(f"no prompt length fills bucket {b}")
        for _ in range(n):
            reqs.append(Request(rid=rid, prompt=rng.integers(0, vocab, plen, dtype=np.int32),
                                max_new_tokens=2, arrival=tick))
            rid += 1
    return reqs, groups


def decode_scopes(eng, cfg, d, dev) -> Dict[str, str]:
    """Instruction name -> scopes of the engine's compiled decode step. A
    TPU trace names each operation by its HLO text, without the
    ``jax.named_scope`` path that the HLO's metadata keeps."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.serve.cache import init_slot_caches

    from . import trace as trace_mod

    sh = SingleDeviceSharding(dev)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    b = d["slots"]
    caches = jax.eval_shape(lambda: init_slot_caches(cfg, b, d["max_len"], eng.cache_dtype,
                                                     d.get("kv_format")))
    key = jax.eval_shape(lambda: jax.random.key(0))
    args = (eng.params, jax.tree.map(sds, caches), sds(jax.ShapeDtypeStruct((b, 1), jnp.int32)),
            sds(jax.ShapeDtypeStruct((b,), jnp.int32)), sds(jax.ShapeDtypeStruct((b,), jnp.bool_)),
            sds(key))
    return trace_mod.hlo_scopes(eng._decode.lower(*args).compile().as_text())


def longest_gap(client: "Client", compiles, gcs: List[tuple]) -> str:
    """The longest gap between two streamed tokens of one request inside
    the window, and what the host did in it."""
    best = None
    for rid, ts in client.times.items():
        a = np.asarray(ts)
        if len(a) < 2:
            continue
        d = np.where((a[:-1] >= client.t_open) & (a[1:] < client.t_close), np.diff(a), -1.0)
        i = int(d.argmax())
        if d[i] > 0 and (best is None or d[i] > best[0]):
            best = (float(d[i]), rid, float(a[i]), float(a[i + 1]))
    if best is None:
        return "no gap inside the window"
    gap, rid, t0, t1 = best
    joins = [client.plen[r] for r, ts in client.times.items() if t0 < ts[0] <= t1]
    gc_in = [(max(a, t0), min(b, t1), g) for a, b, g in gcs if a < t1 and b > t0]
    n_comp = sum(1 for c in compiles if t0 < c[0] <= t1)
    return (f"{gap * 1e3:.3f} ms (request {rid}, {t0 - client.t_open:.3f} s to "
            f"{t1 - client.t_open:.3f} s into the window); first tokens of other requests "
            f"inside it: {len(joins)} (prompt lengths {joins}); garbage collections inside "
            f"it: {len(gc_in)} ({sum(b - a for a, b, _ in gc_in):.4f} s, generations "
            f"{[g for _, _, g in gc_in]}); programs compiled or loaded inside it: {n_comp}")


def counter(name: str) -> Dict[str, float]:
    from repro import obs

    return dict(obs.snapshot()["counters"].get(name, {}))


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


def run_once(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, peaks, backend: str, control: bool = False) -> Dict:
    import jax
    from repro.serve import ContinuousEngine, Request, Scheduler

    from . import trace as trace_mod
    from . import weights

    dev = jax.devices()[0]
    _listen()
    compiles = COMPILES
    n_before = len(compiles)
    calls0, degr0 = counter("gemm.calls"), counter("gemm.degradations")
    cfgd = cell.config["config"]
    d = cell.deploy
    if not cell.traffic.get("stream", True):
        raise ValueError("the harness streams every token through on_token; a mix that "
                         "does not stream needs another client")
    fam = cell.family
    cfg = served_config(cell)
    log(f"[setup] {cell.config['name']}: {fam.weight_bytes(cfgd) / 1e9:.3f} GB of weights, "
        f"KV pool {fam.kv_bytes_per_token(cfgd) * d['slots'] * d['max_len'] / 1e9:.3f} GB "
        f"({d['slots']} slots of {d['max_len']})")
    t = time.perf_counter()
    params = weights.init_params(fam.layout(cfgd), seed)
    jax.block_until_ready(params)
    check_layout(cfg, params)
    log(f"[setup] weights from seed {seed}: {time.perf_counter() - t:.3f}s")

    for k in ("REPRO_PREFILL_CHUNK", "REPRO_PREFIX_CACHE"):
        os.environ.pop(k, None)
    eng = ContinuousEngine(
        cfg=cfg, params=params, n_slots=d["slots"], max_len=d["max_len"],
        temperature=0.0, eos_id=None, kv_format=d.get("kv_format"),
        prefill_chunk=d.get("prefill_chunk"), prefix_cache=bool(d.get("prefix_cache")),
    )
    reqs = traffic.backlog(cell.traffic, d["backlog"], d["slots"], cfgd["vocab"], seed)
    sched = Scheduler(cfg, max_bucket=d["max_len"])
    buckets = sorted({sched.bucket(len(r["prompt"])) for r in reqs})
    rows = [r for r in d["join_rows"] if r <= d["slots"]]
    t = time.perf_counter()
    warm, groups = warmup_requests(Request, sched, buckets, rows, cfgd["vocab"])
    eng.serve(warm, on_token=lambda rid, tok: None)
    log(f"[setup] warm-up: {len(groups)} joins over buckets {buckets} and rows {rows}, "
        f"{time.perf_counter() - t:.3f}s; programs: {_compile_summary(compiles[n_before:])}")

    plen = {r["rid"]: len(r["prompt"]) for r in reqs}
    max_new = {r["rid"]: r["max_new"] for r in reqs}
    trace_dir = None
    plan = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        lead = min(d["trace_lead_s"], seconds / 4)
        plan = {"dir": trace_dir, "lead": lead,
                "seconds": min(d["trace_seconds"], seconds - lead - 0.5)}
    client = Client(plen, d["slots"], seconds, plan)
    served = [Request(rid=r["rid"], prompt=r["prompt"], max_new_tokens=r["max_new"],
                      arrival=r["arrival"]) for r in reqs]
    gclog = GcLog()
    gc.callbacks.append(gclog)
    try:
        eng.serve(served, on_token=client)
    except WindowClosed:
        pass
    else:
        raise InvalidRun("the backlog drained before the window closed")
    finally:
        gc.callbacks.remove(gclog)
    if client.trace_t0 is not None and client.trace_t1 is None:
        client.trace_t1 = time.perf_counter()
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    # -- is this the system under test? -----------------------------------
    in_window = sum(1 for c in compiles if client.t_open <= c[0] < client.t_close)
    backends = sorted({dict(p.split("=", 1) for p in k.split(",") if p)["backend"]
                       for k, v in counter("gemm.calls").items() if v > calls0.get(k, 0)})
    degr = {k: v for k, v in counter("gemm.degradations").items() if v > degr0.get(k, 0)}
    log(f"[window] {seconds}s from t+{client.t_open - t_start:.3f}s; programs compiled "
        f"or loaded inside it: {in_window}; GEMM backends: {backends}; "
        f"degradations: {degr or 'none'}; peak device bytes: {peak}; "
        f"callback time {client.callback_s:.4f}s")
    if in_window:
        raise InvalidRun(f"{in_window} programs compiled or loaded inside the window")
    if backends != [backend] or degr:
        raise InvalidRun(f"serving GEMMs ran on {backends} (degradations {degr}), not {backend!r}")

    run = RunData(cfg=cfgd, family=fam, peaks=peaks, n_slots=d["slots"],
                  setup_s=client.t_open - t_start, t_open=client.t_open,
                  t_close=client.t_close, times=client.times, plen=plen)
    joins = [ts[0] for ts in client.times.values() if client.t_open <= ts[0] < client.t_close]
    attempted = sum(1 for ts in client.times.values()
                    if ts[0] < client.t_close and ts[-1] >= client.t_open)
    log(f"[window] {run.tokens_between(run.t_open, run.t_close)} tokens, "
        f"{len(joins)} joins, {len(run.gaps())} gaps")
    log(f"[window] longest gap {longest_gap(client, compiles, gclog.spans)}")
    entries = cell.end_to_end
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        rec = trace_mod.load(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        red = trace_mod.Reduced(rec, {"decode": decode_scopes(eng, cfg, d, dev)})
        run.trace, run.trace_t0, run.trace_t1 = red, client.trace_t0, client.trace_t1
        entries = cell.per_layer
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = {"device_ops": red.top_ops(), "idle_gaps": red.idle_gaps()}
        log(f"[trace] window {red.window_s:.4f}s, busy {red.busy_s:.4f}s, executions "
            f"{red.count}, module seconds {red.module_s}, GEMM seconds {red.gemm_s}, "
            f"scope seconds { {'/'.join(k): v for k, v in red.scope_s.items()} }, "
            f"prefills {len(red.prefills())}; decode ops named by the HLO's scopes: "
            f"{red.mapped} found, {red.unmapped} not")
    metrics = {}
    for m in entries:
        v = cell.reader(m["name"])(run)
        if v is None:
            log(f"[metrics] {m['name']}: nothing to read in this run")
        else:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # -- free the program's state, then the output check --------------------
    finished = [rid for rid, ts in client.times.items()
                if len(ts) == max_new[rid] and ts[-1] < client.t_close]
    prompts = {r["rid"]: r["prompt"] for r in reqs}
    outputs = {rid: client.tokens[rid] for rid in finished}
    del eng, params, served, warm, client, run
    gc.collect()
    jax.clear_caches()
    check = output_check(cell, seed, prompts, outputs, control)

    out = {"correct": check["correct"], "attempted": attempted, "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = check["checks"]
    return out


def sample(seed: int, outputs: Dict[int, List[int]], want: int) -> List[int]:
    """The finished request with the most served tokens, then others drawn
    from the seed, until ``want`` served tokens are in the sample."""
    if not outputs:
        return []
    rids = sorted(outputs, key=lambda r: (-len(outputs[r]), r))
    pick, n = [rids[0]], len(outputs[rids[0]])
    for rid in traffic.rng_for(seed, 4).permutation(sorted(rids[1:])):
        if n >= want:
            break
        pick.append(int(rid))
        n += len(outputs[int(rid)])
    return pick


def widest_gap(logits: List[np.ndarray], tokens: List[np.ndarray]) -> float:
    """Widest gap, over every position, between the reference's best logit
    and its logit of the token given there."""
    return float(max(np.max(lg.max(-1) - lg[np.arange(len(t)), t]) for lg, t in zip(logits, tokens)))


def output_check(cell: spec.Cell, seed: int, prompts, outputs, control: bool) -> Dict:
    """Widest gap, over the sampled requests' served tokens, between the
    reference's best logit and its logit of the served token.

    With ``control``, the float8 reference is put in the program's place:
    at the same prompts and positions, the token it puts first stands in
    for the served one, through the same comparison."""
    limit = float(cell.deploy["max_logit_gap"])
    pick = sample(seed, outputs, cell.deploy["check_tokens"])
    if not pick:
        log("check no finished request to compare")
        return {"correct": False, "checks": {"max_logit_gap": {"value": None, "limit": limit}}}
    fam = cell.family
    seqs = [np.concatenate([prompts[r], np.asarray(outputs[r][:-1], np.int32)]) for r in pick]
    starts = [len(prompts[r]) - 1 for r in pick]
    t = time.perf_counter()
    logits = fam.logits_at(cell.config["config"], seed, seqs, starts)
    tokens = [np.asarray(outputs[r]) for r in pick]
    log(f"[check] reference over {len(pick)} requests, {sum(map(len, tokens))} served tokens, "
        f"{sum(len(s) for s in seqs)} positions: {time.perf_counter() - t:.3f}s")
    if control:
        log(f"[check] control: the served tokens' own gap is {widest_gap(logits, tokens)!r}; "
            f"the float8 reference's first tokens take their place")
        ctl = fam.logits_at(cell.config["config"], seed, seqs, starts, quant="fp8")
        tokens = [c.argmax(-1) for c in ctl]
    gap = widest_gap(logits, tokens)
    correct = bool(np.isfinite(gap) and gap <= limit)
    log(f"check max_logit_gap {gap!r} limit {limit!r}")
    return {"correct": correct, "checks": {"max_logit_gap": {"value": gap, "limit": limit}}}


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the float8 reference's tokens in place of the served "
                         "ones: the precision control, which has to read correct false")
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        cell = spec.load_cell(root, args.workload)
    except (OSError, KeyError, ValueError) as e:
        log(f"bench: {e}")
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"bench: needs {cell.chips} TPU chip(s); JAX found {devs}")
        return 3
    from .peaks import peaks_for

    peaks = peaks_for(devs[0].device_kind)
    sys.path.insert(0, os.path.join(root, "src"))
    try:
        out = run_once(cell, args.seed, args.seconds, bool(args.trace), t_start=t_start,
                       peaks=peaks, backend="pallas", control=bool(args.control))
    except InvalidRun as e:
        log(f"bench: not the system under test: {e}")
        return 4
    print(json.dumps(out), flush=True)
    return 0
