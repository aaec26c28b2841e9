"""Continuous-batching serving subsystem: correctness + policy tests.

The load-bearing check: greedy outputs from the continuous engine (requests
joining/leaving a shared slot pool mid-flight, bucketed padded prefill,
per-slot decode positions) match single-request ``ServeEngine`` outputs
token-for-token — and the fused decode step compiles exactly once.
"""

import subprocess
import sys
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.models import api
from repro.models.attention import KVCache
from repro.serve import (
    ContinuousEngine,
    Request,
    Scheduler,
    ServeEngine,
    SlotPool,
    bucket_length,
    poisson_trace,
    shared_prefix_trace,
)

KEY = jax.random.key(0)


def _trace(cfg, specs, seed=7):
    """specs: [(prompt_len, max_new, arrival), ...]"""
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=i,
            prompt=[int(x) for x in rng.integers(0, cfg.vocab, p)],
            max_new_tokens=g,
            arrival=a,
        )
        for i, (p, g, a) in enumerate(specs)
    ]


def _reference_outputs(cfg, params, requests, max_len):
    """Each request alone through the lockstep engine (greedy)."""
    eng = ServeEngine(cfg=cfg, params=params, max_len=max_len,
                      cache_dtype=jnp.float32)
    out = {}
    for r in requests:
        toks = eng.generate(
            {"tokens": jnp.asarray([r.prompt], jnp.int32)}, r.max_new_tokens
        )
        out[r.rid] = [int(t) for t in np.asarray(toks[0])]
    return out


# ---------------------------------------------------------------------------
# engine correctness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "arch",
    [
        "chatglm3-6b",  # attention-only: pow2 buckets, padded prefill
        "jamba-v0.1-52b",  # mamba+moe: auto exact-length buckets
    ],
)
def test_continuous_matches_single_request_greedy(arch):
    """Token-for-token match under mid-flight joins/leaves + staggered
    arrivals, with exactly one compiled decode program."""
    cfg = ARCHS[arch].reduced()
    params = api.init_params(cfg, KEY)
    max_len = 48
    specs = [(7, 5, 0), (12, 9, 0), (7, 3, 2), (16, 11, 5), (12, 1, 9)]
    requests = _trace(cfg, specs)
    want = _reference_outputs(cfg, params, requests, max_len)

    eng = ContinuousEngine(
        cfg=cfg, params=params, n_slots=2, max_len=max_len,
        cache_dtype=jnp.float32,
    )
    report = eng.serve(requests)
    for r in requests:
        assert report.outputs[r.rid] == want[r.rid], r.rid
    # Requests joined and left a 2-slot pool (5 requests, mixed lengths)
    # without the fused decode step ever recompiling.
    n = eng.decode_compilations()
    if n is not None:
        assert n == 1
    assert report.prefill_batches >= 2
    assert 0 < report.mean_occupancy <= 1.0
    assert report.generated_tokens == sum(g for _, g, _ in specs)


def test_continuous_streams_and_stops_on_eos():
    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    requests = _trace(cfg, [(7, 12, 0), (12, 12, 0)])
    # Find a token the first request actually emits, then use it as EOS.
    base = ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=32,
                            cache_dtype=jnp.float32)
    full = base.serve(requests)
    eos = full.outputs[0][2]  # 3rd emitted token of request 0

    streamed = []
    eng = ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=32,
                           cache_dtype=jnp.float32, eos_id=eos)
    report = eng.serve(
        requests, on_token=lambda rid, tok: streamed.append((rid, tok))
    )
    out0 = report.outputs[0]
    assert out0 == full.outputs[0][: len(out0)]
    assert out0[-1] == eos and len(out0) <= 3
    # every output token was streamed, in order
    for r in requests:
        got = [t for rid, t in streamed if rid == r.rid]
        assert got == report.outputs[r.rid]


def test_continuous_chunked_prefill_matches_greedy():
    """Chunked prefill alone (no prefix cache): token-for-token agreement
    with the monolithic-prefill engine, one compiled decode program."""
    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    specs = [(7, 5, 0), (23, 6, 0), (12, 4, 2), (30, 5, 4)]
    requests = _trace(cfg, specs)
    base = ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=48,
                            cache_dtype=jnp.float32)
    want = base.serve(requests).outputs

    eng = ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=48,
                           cache_dtype=jnp.float32, prefill_chunk=8)
    report = eng.serve(requests)
    assert report.outputs == want
    n = eng.decode_compilations()
    if n is not None:
        assert n == 1


def test_continuous_prefix_cache_matches_greedy_and_hits():
    """Prefix cache + chunked prefill on a shared-system-prompt trace:
    bitwise-identical greedy tokens vs the features-off engine, cache hits
    observed, decode still compiles exactly once."""
    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    requests = shared_prefix_trace(
        6, seed=3, vocab=cfg.vocab, prefix_len=48, tail_lens=(5, 9),
        gen_lens=(4, 6), mean_interarrival=1.0,
    )
    base = ContinuousEngine(cfg=cfg, params=params, n_slots=3, max_len=96,
                            cache_dtype=jnp.float32)
    want = base.serve(requests).outputs

    eng = ContinuousEngine(
        cfg=cfg, params=params, n_slots=3, max_len=96,
        cache_dtype=jnp.float32, prefill_chunk=16, prefix_cache=True,
        prefix_block=16,
    )
    report = eng.serve(requests)
    assert report.outputs == want  # bitwise greedy agreement, cache on vs off
    n = eng.decode_compilations()
    if n is not None:
        assert n == 1  # joins resumed from cache never recompiled decode
    stats = eng.prefix_cache_stats()
    assert stats["hits"] > 0 and stats["misses"] >= 1
    assert stats["cached_tokens"] > 0


def test_continuous_quant_pool_prefix_cache_serves():
    """Quantized slot pool + quantized prefix trie: the run completes with
    hits and the cold request (no cached prefix exists yet) matches the
    cache-off engine exactly — later requests adopt the prefix's original
    scales, which legitimately differ from a fresh whole-prompt
    calibration, so their tokens are compared only for shape."""
    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    requests = shared_prefix_trace(
        4, seed=5, vocab=cfg.vocab, prefix_len=32, tail_lens=(5, 7),
        gen_lens=(4,), mean_interarrival=2.0,
    )
    base = ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=64,
                            cache_dtype=jnp.float32, kv_format="int8")
    want = base.serve(requests).outputs

    eng = ContinuousEngine(
        cfg=cfg, params=params, n_slots=2, max_len=64,
        cache_dtype=jnp.float32, kv_format="int8",
        prefill_chunk=16, prefix_cache=True, prefix_block=16,
    )
    report = eng.serve(requests)
    assert report.outputs[0] == want[0]  # cold request: identical path
    assert {r: len(t) for r, t in report.outputs.items()} == {
        r: len(t) for r, t in want.items()
    }
    assert eng.prefix_cache_stats()["hits"] > 0


def test_chunked_prefill_env_knobs_and_validation():
    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    with pytest.raises(ValueError):
        ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=32,
                         prefill_chunk=12)  # not a power of two
    env = {"REPRO_PREFILL_CHUNK": "16", "REPRO_PREFIX_CACHE": "1"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        eng = ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=32)
        assert eng.prefill_chunk == 16 and eng.prefix_cache
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.update({k: v})
    # prefix cache alone implies a default chunk width
    eng = ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=32,
                           prefix_cache=True)
    assert eng.prefill_chunk is not None
    # recurrent mixers can't resume mid-prompt: both features disable
    with pytest.warns(RuntimeWarning, match="attention-only"):
        eng = ContinuousEngine(
            cfg=ARCHS["jamba-v0.1-52b"].reduced(), params=None,
            n_slots=2, max_len=32, prefill_chunk=8, prefix_cache=True,
        )
    assert eng.prefill_chunk is None and not eng.prefix_cache


def test_attr_fallback_recaptures_untraced_step():
    """A compiled step whose trace ran while metrics were off must not
    silently attribute zero GEMM-seconds forever: the engine re-captures
    its workload via jax.eval_shape and counts on gemm.attr_fallback."""
    from repro import obs

    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    eng = ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=32,
                           cache_dtype=jnp.float32)
    requests = _trace(cfg, [(7, 4, 0), (12, 4, 1)])
    prev = obs.set_enabled(False)
    try:
        eng.serve(requests)  # traces + compiles with capture recording off
    finally:
        obs.set_enabled(prev)
    assert eng._prefill_workloads == {}  # nothing attributed while off

    obs.reset()
    eng.serve(_trace(cfg, [(7, 4, 0), (12, 4, 1)], seed=11))
    assert ("decode",) in eng._prefill_workloads  # re-captured via eval_shape
    snap = obs.snapshot()["counters"].get("gemm.attr_fallback", {})
    assert sum(snap.values()) >= 1


def _engine_spans(eng, requests, tmp_path, **kw):
    """Serve under ``jax.profiler`` and read the trace back through the
    benchmark's loader: (report, [(start, end, name)] of the ``serve.*``
    host spans in order, {program name: the kind the reduction gives it})."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
    try:
        from lib import trace
    finally:
        sys.path.pop(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        report = eng.serve(requests, **kw)
    finally:
        jax.profiler.stop_trace()
    rec = trace.load(trace.find_xplane(str(tmp_path)))
    spans = [(s, s + d, n) for s, d, n, *_ in rec["host"]
             if n.startswith("serve.")]
    names = {m[2].split("(")[0] for m in rec["modules"]}
    return report, spans, {m: trace.module_kind(m) for m in names}


@pytest.mark.parametrize("mode", ["stream", "deferred", "chunked"])
def test_engine_spans_on_the_profiler_clock(mode, tmp_path):
    """Each phase of a tick is one sibling span on the profiler's clock.
    Streaming, every decode tick reads decode -> readback -> emit ->
    telemetry and a join tick puts admit -> prefill before it; deferred,
    readback and emit are absent. No engine span encloses another, and only
    the engine's step programs carry a name the trace reduction maps to a
    kind."""
    import re

    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    eng = ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=48,
                           cache_dtype=jnp.float32,
                           prefill_chunk=8 if mode == "chunked" else None)
    specs = [(7, 4, 0), (12, 3, 0), (9, 3, 0)]
    kw = {} if mode == "deferred" else {"on_token": lambda rid, tok: None}
    eng.serve(_trace(cfg, specs), **kw)  # compile outside the profile
    report, spans, kinds = _engine_spans(
        eng, _trace(cfg, specs, seed=3), tmp_path, **kw)

    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:]))  # siblings
    names = [n.split(".", 1)[1] for _, _, n in spans]
    assert names.count("decode") == report.decode_steps > 0
    tick = ("decode,telemetry," if mode == "deferred"
            else "decode,readback,emit,telemetry,")
    if mode == "chunked":
        assert names.count("chunk") >= report.prefill_batches
        assert "prefill" not in names
    else:
        assert re.fullmatch(f"((admit,prefill,)*{tick})+", ",".join(names) + ",")
        assert (names.count("prefill") == names.count("admit")
                == report.prefill_batches)
    step = "jit_engine_chunk" if mode == "chunked" else "jit_engine_prefill"
    assert {m: k for m, k in kinds.items() if k} == {
        "jit_engine_decode": "decode", step: step.rsplit("_", 1)[1]}


def test_metrics_off_serves_with_bare_spans(tmp_path):
    """``REPRO_METRICS=0``: the engine serves the same tokens, and its spans
    are bare (nothing reaches the profile)."""
    from repro import obs

    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    eng = ContinuousEngine(cfg=cfg, params=params, n_slots=2, max_len=48,
                           cache_dtype=jnp.float32)
    specs = [(7, 4, 0), (12, 3, 0), (9, 3, 1)]
    streamed = {}
    want = eng.serve(_trace(cfg, specs), on_token=lambda rid, tok: None).outputs
    prev = obs.set_enabled(False)
    try:
        report, spans, _ = _engine_spans(
            eng, _trace(cfg, specs), tmp_path,
            on_token=lambda rid, tok: streamed.setdefault(rid, []).append(tok))
    finally:
        obs.set_enabled(prev)
    assert spans == []
    assert report.outputs == want == streamed


def test_decode_at_matches_decode_lockstep():
    cfg = ARCHS["qwen2.5-32b"].reduced()  # qkv_bias: bias-preload decode path
    params = api.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    logits, caches = api.prefill(cfg, params, {"tokens": toks}, max_len=32,
                                 cache_dtype=jnp.float32)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    l_lock, _ = api.decode(cfg, params, tok, caches, jnp.asarray(16, jnp.int32))
    l_slot, _ = api.decode_at(cfg, params, tok, caches,
                              jnp.full((2,), 16, jnp.int32))
    np.testing.assert_array_equal(np.asarray(l_lock), np.asarray(l_slot))


def test_prefill_bucketed_matches_exact_prefill():
    """Right-padding + per-row last-token gather == unpadded prefill."""
    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    t7 = jax.random.randint(jax.random.key(2), (1, 7), 0, cfg.vocab)
    t12 = jax.random.randint(jax.random.key(3), (1, 12), 0, cfg.vocab)
    toks = np.zeros((2, 16), np.int32)
    toks[0, :7] = np.asarray(t7[0])
    toks[1, :12] = np.asarray(t12[0])
    lb, _ = api.prefill_bucketed(
        cfg, params, jnp.asarray(toks), jnp.asarray([7, 12], jnp.int32),
        cache_dtype=jnp.float32,
    )
    for row, t in ((0, t7), (1, t12)):
        le, _ = api.prefill(cfg, params, {"tokens": t}, max_len=t.shape[1],
                            cache_dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(lb[row]), np.asarray(le[0]), rtol=1e-5, atol=1e-5
        )


def test_serve_engine_temperature_key_plumbing():
    """Satellite regression: sampling is deterministic per key and the first
    token responds to the key (it is sampled from a fresh split, not the
    parent key that step 0 re-splits)."""
    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    eng = ServeEngine(cfg=cfg, params=params, max_len=24,
                      cache_dtype=jnp.float32, temperature=1.0)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 8), 0, cfg.vocab)}
    a = np.asarray(eng.generate(batch, 8, key=jax.random.key(5)))
    b = np.asarray(eng.generate(batch, 8, key=jax.random.key(5)))
    c = np.asarray(eng.generate(batch, 8, key=jax.random.key(6)))
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()


# ---------------------------------------------------------------------------
# slot pool
# ---------------------------------------------------------------------------


def test_slot_pool_lease_bookkeeping():
    cfg = ARCHS["chatglm3-6b"].reduced()
    pool = SlotPool.create(cfg, n_slots=3, max_len=16, dtype=jnp.float32)
    assert pool.n_free == 3 and pool.occupancy == 0.0
    slots = pool.allocate(["a", "b"])
    assert slots == [0, 1] and pool.n_free == 1
    assert pool.owner_of(0) == "a" and pool.active_slots() == [0, 1]
    assert pool.release(0) is True
    assert pool.n_free == 2 and pool.owner_of(0) is None
    assert pool.allocate(["c"]) == [0]  # recycled lowest slot first
    with pytest.raises(RuntimeError):
        pool.allocate(["d", "e", "f"])  # only 1 free
    # releasing a free (never- or already-released) slot is an idempotent
    # no-op — the evict sweep may race a same-tick retire — but an
    # out-of-range slot is a caller bug and still raises.
    assert pool.release(2) is False  # never leased
    assert pool.release(0) is True
    assert pool.release(0) is False  # double release: no-op, slot not re-freed
    assert pool.n_free == 2
    with pytest.raises(KeyError):
        pool.release(17)  # out of range


def test_slot_pool_join_scatters_only_target_slots():
    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, KEY)
    pool = SlotPool.create(cfg, n_slots=3, max_len=16, dtype=jnp.float32)
    toks = jax.random.randint(jax.random.key(4), (1, 8), 0, cfg.vocab)
    _, pre = api.prefill_bucketed(
        cfg, params, toks, jnp.asarray([8], jnp.int32), cache_dtype=jnp.float32
    )
    pool.allocate(["r0"])  # slot 0 leased to someone else
    slots = pool.allocate(["r1"])
    assert slots == [1]
    pool.join(pre, slots)
    for pc, fc in zip(pool.caches, pre):
        if isinstance(pc, KVCache):
            got = np.asarray(pc.k[:, 1, :8])
            np.testing.assert_array_equal(got, np.asarray(fc.k[:, 0]))
            # untouched slots stay zero
            assert not np.asarray(pc.k[:, 0]).any()
            assert not np.asarray(pc.k[:, 2]).any()


# ---------------------------------------------------------------------------
# scheduler policy
# ---------------------------------------------------------------------------


def test_bucket_length_rounding():
    assert bucket_length(3) == 8  # floor
    assert bucket_length(8) == 8
    assert bucket_length(9) == 16
    assert bucket_length(17) == 32
    assert bucket_length(17, exact=True) == 17
    assert bucket_length(17, maximum=24) == 24  # clamped, still >= n
    assert bucket_length(30, maximum=24) == 30  # never below the prompt


def _mk_sched(cfg, reqs, **kw):
    s = Scheduler(cfg, **kw)
    for r in reqs:
        s.submit(r)
    return s


def test_scheduler_fifo_bucketed_admission():
    cfg = ARCHS["chatglm3-6b"].reduced()
    reqs = [
        Request(rid=0, prompt=[1] * 7, max_new_tokens=4),   # bucket 8
        Request(rid=1, prompt=[1] * 12, max_new_tokens=4),  # bucket 16
        Request(rid=2, prompt=[1] * 6, max_new_tokens=4),   # bucket 8
        Request(rid=3, prompt=[1] * 15, max_new_tokens=4),  # bucket 16
    ]
    sched = _mk_sched(cfg, reqs)
    # Head-of-line is rid 0 (bucket 8); rid 2 rides along, 1/3 keep position.
    b1 = sched.next_batch(4, now=0)
    assert [r.rid for r in b1] == [0, 2]
    b2 = sched.next_batch(1, now=0)  # only one slot free
    assert [r.rid for r in b2] == [1]
    b3 = sched.next_batch(4, now=0)
    assert [r.rid for r in b3] == [3]
    assert sched.next_batch(4, now=0) == []


def test_scheduler_unadmittable_head_falls_through_to_deepest_bucket():
    """Starvation regression: an un-admittable head-of-line request must not
    pin arrived requests of other buckets behind it while slots sit free.
    Admission falls through to the deepest non-empty admissible bucket; the
    blocked head keeps its queue position."""
    cfg = ARCHS["chatglm3-6b"].reduced()
    reqs = [
        Request(rid=0, prompt=[1] * 30, max_new_tokens=4),  # bucket 32 (head)
        Request(rid=1, prompt=[1] * 6, max_new_tokens=4),   # bucket 8
        Request(rid=2, prompt=[1] * 12, max_new_tokens=4),  # bucket 16
        Request(rid=3, prompt=[1] * 7, max_new_tokens=4),   # bucket 8
    ]
    sched = _mk_sched(cfg, reqs)
    blocked = lambda r: len(r.prompt) <= 16  # head (30) not admissible
    b1 = sched.next_batch(4, now=0, admissible=blocked)
    assert [r.rid for r in b1] == [2]  # deepest admissible bucket (16) first
    b2 = sched.next_batch(4, now=0, admissible=blocked)
    assert [r.rid for r in b2] == [1, 3]
    # head becomes admissible again: strict FIFO resumes
    b3 = sched.next_batch(4, now=0)
    assert [r.rid for r in b3] == [0]


def test_scheduler_no_starvation_ticks():
    """Simulated engine tick loop: at every tick with a free slot and at
    least one arrived admissible request, admission must make progress —
    the free-slots-while-admissible-queue-waits tick count stays zero."""
    cfg = ARCHS["chatglm3-6b"].reduced()
    rng = np.random.default_rng(2)
    reqs = [
        Request(
            rid=i, prompt=[1] * int(rng.choice([6, 12, 25, 30])),
            max_new_tokens=2, arrival=int(rng.integers(0, 6)),
        )
        for i in range(12)
    ]
    sched = _mk_sched(cfg, reqs)
    free = 3
    in_flight = []  # (rid, ticks_left)
    admissible = lambda r: len(r.prompt) <= 16  # long prompts never admit
    n_admissible = sum(1 for r in reqs if admissible(r))
    starved_ticks = 0
    done = 0
    for now in range(200):
        while free > 0:
            batch = sched.next_batch(free, now, admissible=admissible)
            if not batch:
                break
            free -= len(batch)
            in_flight.extend((r.rid, 2) for r in batch)
        waiting = sum(
            1 for r in sched._queue if r.arrival <= now and admissible(r)
        )
        if free > 0 and waiting:
            starved_ticks += 1
        nxt = []
        for rid, left in in_flight:
            if left - 1 == 0:
                free += 1
                done += 1
            else:
                nxt.append((rid, left - 1))
        in_flight = nxt
    assert starved_ticks == 0
    assert done == n_admissible  # every admissible request ran to completion


def test_scheduler_arrival_gating_and_eviction():
    cfg = ARCHS["chatglm3-6b"].reduced()
    reqs = [
        Request(rid=0, prompt=[1] * 8, max_new_tokens=2, arrival=3),
        Request(rid=1, prompt=[1] * 8, max_new_tokens=5, arrival=0),
    ]
    sched = _mk_sched(cfg, reqs, eos_id=99)
    assert sched.next_batch(2, now=2) == [reqs[1]]  # rid 0 not arrived yet
    batch = sched.next_batch(2, now=3)
    assert batch == [reqs[0]]
    sched.admit([reqs[1]], [0], now=0)
    sched.admit([reqs[0]], [1], now=3)
    assert not sched.record_token(1, 7, now=1)
    assert sched.record_token(1, 99, now=2)  # EOS evicts before budget
    assert sched.states[1].done and sched.states[1].tokens == [7, 99]
    assert not sched.record_token(0, 5, now=4)
    assert sched.record_token(0, 6, now=5)  # max_new_tokens evicts
    assert sched.drained


def test_scheduler_exact_buckets_for_recurrent_families():
    assert Scheduler(ARCHS["jamba-v0.1-52b"].reduced()).exact_buckets
    assert Scheduler(ARCHS["xlstm-125m"].reduced()).exact_buckets
    assert not Scheduler(ARCHS["chatglm3-6b"].reduced()).exact_buckets


def test_poisson_trace_deterministic_and_sorted():
    a = poisson_trace(8, seed=3, mean_interarrival=2.0)
    b = poisson_trace(8, seed=3, mean_interarrival=2.0)
    assert [(r.prompt, r.arrival, r.max_new_tokens) for r in a] == [
        (r.prompt, r.arrival, r.max_new_tokens) for r in b
    ]
    assert all(x.arrival <= y.arrival for x, y in zip(a, a[1:]))


# ---------------------------------------------------------------------------
# benchmark acceptance: continuous strictly beats static on a mixed trace
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serving_bench_smoke_continuous_wins(tmp_path):
    out = tmp_path / "BENCH_serving.json"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks", "serving_bench.py"),
         "--smoke", "--out", str(out)],
        capture_output=True, text=True, env=env, cwd=repo,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    result = json.loads(out.read_text())
    c, s = result["continuous"], result["static"]
    assert result["speedup_tokens_per_step"] > 1.0
    assert result["occupancy_gain"] > 0.0
    # tokens/step and occupancy are deterministic; tokens/sec is wall clock
    # on a tiny smoke trace, so on a loaded machine the continuous engine's
    # win can be eaten by scheduling noise — require same order of
    # magnitude only, the strict win is asserted on the step-count metric.
    assert c["tokens_per_sec"] > 0.7 * s["tokens_per_sec"]
    # None when this JAX version hides the jit cache size
    assert c["decode_compilations"] in (None, 1)
    assert c["useful_tokens"] == s["useful_tokens"]  # same trace, same work
