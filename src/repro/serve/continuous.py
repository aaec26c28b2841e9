"""Continuous-batching serving engine: keep every decode lane busy.

The static ``ServeEngine`` admits a batch, decodes until the *longest*
request finishes, and only then admits more — decode GEMMs shrink as
requests retire, starving the engine exactly the way low-utilization
baselines starve their MAC arrays in the paper. ``ContinuousEngine``
instead drives **one fused jit decode step over a fixed slot pool with an
active-slot mask**: a finished request frees its slot mid-flight, the next
queued request is prefilled (length-bucketed compiled steps) and scattered
in, and the decode step never recompiles — a masked slot costs one batch
lane, not a new program. Slot occupancy is the serving analogue of the
paper's FPU utilization, and the engine reports it next to tokens/sec.

Step loop (one tick = one fused decode dispatch):

1. **join** — while slots are free and arrived requests queue, prefill one
   prompt-length bucket (``api.prefill_bucketed``), sample each request's
   first token from its last-real-token logits, scatter caches into leased
   slots (`SlotPool.join`), and point the lanes at their positions.
2. **decode** — one jit'd ``decode_at`` + sample over all ``n_slots`` lanes
   (inactive lanes are masked: they hold their token and position).
3. **evict** — stream each active lane's sampled token to its request;
   EOS / max-token requests retire and free their slot for the next tick.

Two compounding prompt-side optimizations (attention-only patterns, both
off by default — ``prefill_chunk`` / ``prefix_cache`` fields or the
``REPRO_PREFILL_CHUNK`` / ``REPRO_PREFIX_CACHE`` env knobs):

* **Chunked prefill** — instead of one monolithic bucket prefill that
  stalls every in-flight decode for the length of the longest prompt, a
  join becomes a *pending pipeline*: its standalone caches advance by one
  fixed power-of-two chunk (``api.prefill_chunk``) per tick, interleaved
  with the pool's decode steps, and the batch joins the pool only when
  every row's prompt is consumed. Chunk width and bucket are compile-time
  shapes; per-row offsets are data — the chunk step compiles once per
  (rows, bucket, width), and the decode step still never recompiles.
* **Prefix cache** — a radix trie over token-id blocks
  (``serve.cache.PrefixCache``) remembers finished prompts' K/V. A new
  request attaches its longest cached prefix (snapped down to a chunk
  boundary — resume offsets stay chunk-aligned) directly into its
  standalone caches and chunk-prefills only the suffix; quantized pools
  re-quantize the attached span under the prefix's original scales
  (scale adoption — see ``quant.kvcache``). Emits
  ``serve.prefix_cache.{hits,misses,evictions,cached_tokens}``.

Every request's lifecycle — arrival, admission (incl. fall-through bucket),
prefix attach, chunk ticks, first token, each ITL, retirement — is stamped
into :mod:`repro.obs.tracing` keyed by ``Request.uid`` (host-side only; the
compiled decode step is bit-identical with tracing on or off). The
``queue``/``prefix_attach``/``chunk_prefill`` phases are contiguous and
share the TTFT stamps, so the exported Perfetto timeline decomposes each
``serve.ttft_seconds`` sample exactly. ``slo_ttft_ms``/``slo_itl_ms`` (or
``REPRO_SLO_TTFT_MS``/``REPRO_SLO_ITL_MS``) turn those stamps into
``ServingReport.goodput``.

Each phase of a tick runs inside an ``obs.span``: ``serve.admit``
(scheduling and admission stamps), ``serve.prefill`` (one monolithic join),
``serve.chunk`` (one chunk-pipeline advance and its completion),
``serve.decode`` (the step's dispatch), ``serve.readback`` (the wait for the
step's tokens), ``serve.emit`` (``on_token`` and retirement) and
``serve.telemetry``. They are siblings, never nested, and land on the
profiler's clock, so any ``jax.profiler`` capture of a serving process names
what the host was doing in each idle gap of the device. The deferred path
has no readback or emit.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.obs import attr as _attr
from repro.obs import tracing as _tracing
from repro.configs.base import ArchConfig
from repro.models import api as model_api

from .cache import PrefixCache, SlotPool
from .engine import sample_token
from .scheduler import Request, Scheduler

__all__ = ["ContinuousEngine", "ServingReport"]

# Chunk-prefill pipelines in flight at once. One is enough to kill
# head-of-line blocking (decode never waits on a monolithic prefill) while
# keeping slot reservations — leased but not yet active — bounded.
_MAX_PENDING = 1


@dataclasses.dataclass
class _PendingJoin:
    """A join mid-chunk: standalone caches filling one chunk per tick."""

    batch: List[Request]
    slots: List[int]
    caches: Any  # standalone full-precision caches [P, rows, lb, ...]
    rows: int
    lb: int
    offsets: np.ndarray  # [len(batch)] next fill position per row
    plens: np.ndarray  # [len(batch)] prompt lengths
    nodes: List[list]  # per-row acquired trie nodes (release at completion)
    floors: Any  # scale_floors for the quantized pool join (or None)
    first_logits: Optional[jax.Array] = None  # [rows, V]; valid where done
    done: Optional[np.ndarray] = None  # [len(batch)] row consumed its prompt

    def __post_init__(self) -> None:
        if self.done is None:
            self.done = np.zeros(len(self.batch), bool)

    @property
    def all_done(self) -> bool:
        return bool(self.done.all())


class _Lifecycle:
    """Per-``serve()`` request-lifecycle bookkeeping.

    Owns the wall stamps the report's product fields (TTFT/ITL and phase
    percentiles, goodput) are computed from, emits the phase histograms,
    and mirrors every lifecycle edge into :mod:`repro.obs.tracing`. The
    phase chain — queue → prefix_attach → chunk_prefill → decode (chunked)
    or queue → prefill → decode (monolithic) — is contiguous and shares
    these exact stamps, so each request's pre-decode phase durations sum
    to its ``serve.ttft_seconds`` sample by construction, which is what
    makes the exported timeline trustworthy as a TTFT decomposition.
    """

    def __init__(self) -> None:
        self.arrive: Dict[int, float] = {}  # rid -> clock-start stamp
        self.admit: Dict[int, float] = {}  # rid -> queue-exit stamp
        self.attach: Dict[int, float] = {}  # rid -> attach-done stamp
        self.last_tok: Dict[int, float] = {}
        self.ttfts: List[float] = []
        self.itls: List[float] = []
        self.queue_s: List[float] = []
        self.attach_s: List[float] = []
        self.chunk_s: List[float] = []
        self.ttft_by_rid: Dict[int, float] = {}
        self.itl_max: Dict[int, float] = {}

    def arrival(self, r: Request, ts: float) -> None:
        """Queue enter: the loop reached the request's arrival tick."""
        self.arrive[r.rid] = ts
        _tracing.begin_request(r.uid, r.rid, ts)

    def admitted(
        self, batch: List[Request], ts: float, bucket, fallthrough: bool,
        phase: str,
    ) -> None:
        """Queue exit: the scheduler popped ``batch`` for one join."""
        for r in batch:
            q = ts - self.arrive.get(r.rid, ts)
            self.admit[r.rid] = ts
            self.queue_s.append(q)
            _obs.histogram("serve.queue_seconds").observe(q)
            _tracing.annotate(r.uid, bucket=bucket, fallthrough=fallthrough)
            _tracing.instant(
                r.uid, "admitted", ts,
                bucket=bucket, fallthrough=fallthrough, queue_s=q,
            )
            _tracing.begin_phase(r.uid, phase, ts)

    def attached(self, batch: List[Request], ts: float) -> None:
        """Chunked path: slots leased + cached prefixes attached; the
        chunk-prefill pipeline owns the request from here to first token."""
        for r in batch:
            a = ts - self.admit.get(r.rid, ts)
            self.attach[r.rid] = ts
            self.attach_s.append(a)
            _obs.histogram("serve.prefill_attach_seconds").observe(a)
            _tracing.begin_phase(r.uid, "chunk_prefill", ts)

    def first_token(
        self, batch: List[Request], sched: Scheduler, eos_id, ts: float,
        chunked: bool,
    ) -> None:
        """First token sampled (from prefill logits, at join): closes the
        TTFT window and the last pre-decode phase with the same stamp."""
        _obs.counter("serve.requests", event="admitted").inc(len(batch))
        for r in batch:
            ttft = ts - self.arrive.get(r.rid, ts)
            self.ttfts.append(ttft)
            self.ttft_by_rid[r.rid] = ttft
            self.last_tok[r.rid] = ts
            _obs.histogram("serve.ttft_seconds").observe(ttft)
            if chunked:
                c = ts - self.attach.get(r.rid, ts)
                self.chunk_s.append(c)
                _obs.histogram("serve.chunk_prefill_seconds").observe(c)
            _tracing.instant(r.uid, "first_token", ts, ttft_s=ttft)
            _tracing.begin_phase(r.uid, "decode", ts)
            st = sched.states[r.rid]
            if st.done:  # one-token request: retires at its own join tick
                _obs.counter("serve.requests", event="retired").inc()
                reason = (
                    "eos"
                    if eos_id is not None and st.tokens
                    and st.tokens[-1] == eos_id
                    else "budget"
                )
                self.retired(r, st, reason, ts)

    def token(self, r: Request, ts: float) -> None:
        prev = self.last_tok.get(r.rid)
        if prev is not None:
            itl = ts - prev
            self.itls.append(itl)
            self.itl_max[r.rid] = max(self.itl_max.get(r.rid, 0.0), itl)
            _obs.histogram("serve.itl_seconds").observe(itl)
            _tracing.instant(r.uid, "token", ts, itl_s=itl)
        self.last_tok[r.rid] = ts

    def retired(self, r: Request, st, reason: str, ts: float) -> None:
        _obs.event(
            "request_retired", uid=r.uid, rid=r.rid, reason=reason,
            tokens=st.n_emitted, slot=st.slot,
        )
        _tracing.end_request(r.uid, reason, ts)

    def goodput(self, requests: List[Request], slo_ttft_s, slo_itl_s):
        """Fraction of requests meeting every configured SLO; None when no
        SLO is set (absence of an objective must not read as 100%)."""
        if (slo_ttft_s is None and slo_itl_s is None) or not requests:
            return None
        good = 0
        for r in requests:
            ok = True
            if slo_ttft_s is not None:
                ttft = self.ttft_by_rid.get(r.rid)
                ok = ok and ttft is not None and ttft <= slo_ttft_s
            if slo_itl_s is not None:
                ok = ok and self.itl_max.get(r.rid, 0.0) <= slo_itl_s
            good += bool(ok)
        return good / len(requests)


@dataclasses.dataclass
class ServingReport:
    """Outcome + the utilization counters the paper's story maps onto."""

    outputs: Dict[int, List[int]]  # rid -> generated tokens
    generated_tokens: int
    decode_steps: int
    prefill_batches: int
    mean_occupancy: float  # mean active-slot fraction per decode step
    wall_time_s: float
    kv_bytes_per_slot: float = 0.0  # K/V pool bytes per slot (+ quant scales)
    # Host-observed latency percentiles (seconds), or ``None`` when the run
    # produced no samples — "no data" must never masquerade as "zero
    # latency" (JSON renders it as null). TTFT = wall clock from the
    # request's arrival tick to its first token (sampled from prefill logits
    # at join, so queueing + prefill dominate); ITL = wall clock between a
    # lane's consecutive tokens. On the deferred-detokenization path (no EOS,
    # no streaming callback) decode dispatches are async, so ITL measures
    # host dispatch cadence, not device step latency — the sync path (EOS or
    # ``on_token``) measures true token-to-token wall time.
    ttft_p50: Optional[float] = None
    ttft_p99: Optional[float] = None
    itl_p50: Optional[float] = None
    itl_p99: Optional[float] = None
    # SLO / phase decomposition. ``goodput`` = fraction of requests whose
    # TTFT (and worst ITL) met every configured objective (engine
    # ``slo_ttft_ms``/``slo_itl_ms`` fields or ``REPRO_SLO_TTFT_MS`` /
    # ``REPRO_SLO_ITL_MS``); None when no SLO is set. ``queue_*`` is the
    # arrival -> admission wait; ``attach_*`` / ``chunk_prefill_*`` are the
    # chunked path's prefix-attach and chunk-prefill phases (None on the
    # monolithic path, whose single pre-decode phase is TTFT - queue).
    # The three phases are contiguous and share the TTFT stamps, so per
    # request they sum exactly to its ``serve.ttft_seconds`` sample.
    # ``slot_hwm`` = peak concurrently-leased slots (capacity headroom).
    goodput: Optional[float] = None
    queue_p50: Optional[float] = None
    queue_p99: Optional[float] = None
    attach_p50: Optional[float] = None
    attach_p99: Optional[float] = None
    chunk_prefill_p50: Optional[float] = None
    chunk_prefill_p99: Optional[float] = None
    slot_hwm: int = 0

    @property
    def tokens_per_sec(self) -> float:
        return self.generated_tokens / self.wall_time_s if self.wall_time_s else 0.0

    @property
    def tokens_per_step(self) -> float:
        """Useful tokens per decode dispatch — the deterministic (wall-clock
        free) throughput proxy; == n_slots * mean occupancy up to the tokens
        sampled directly from prefill logits."""
        return self.generated_tokens / self.decode_steps if self.decode_steps else 0.0


@dataclasses.dataclass
class ContinuousEngine:
    """Continuous-batching engine over ``n_slots`` pooled decode lanes.

    LM families only (dense / moe / hybrid / ssm): requests are token
    prompts. The static ``ServeEngine`` remains the simple lockstep path
    (and the audio/VLM entry point).
    """

    cfg: ArchConfig
    params: Any
    n_slots: int
    max_len: int
    cache_dtype: Any = jnp.bfloat16
    temperature: float = 0.0
    eos_id: Optional[int] = None
    exact_buckets: Optional[bool] = None  # None = auto (exact iff recurrent)
    # Narrow K/V lanes for the slot pool ("int8" / "fp8_e4m3" / "fp8_e5m2"):
    # ~4x less cache memory per slot (vs fp32 lanes), so the same HBM budget
    # admits proportionally more slots. Prefill stays full-precision; the
    # join scatter calibrates per-slot scales and quantizes (see serve.cache).
    kv_format: Optional[str] = None
    # Chunked prefill width (power of two; None = env REPRO_PREFILL_CHUNK,
    # unset = off). Attention-only patterns; see the module docstring.
    prefill_chunk: Optional[int] = None
    # Prefix cache (None = env REPRO_PREFIX_CACHE, unset = off). Enabling it
    # implies chunked prefill (suffix-only prefill needs the chunk entry);
    # the trie persists across serve() calls for the engine's lifetime.
    prefix_cache: Optional[bool] = None
    prefix_block: int = 16  # trie block size, tokens
    prefix_capacity: int = 1 << 16  # trie capacity, tokens
    # TTFT / worst-ITL service-level objectives in milliseconds (None = env
    # REPRO_SLO_TTFT_MS / REPRO_SLO_ITL_MS, unset = no SLO). With at least
    # one set, ServingReport.goodput is the fraction of requests meeting
    # every configured objective.
    slo_ttft_ms: Optional[float] = None
    slo_itl_ms: Optional[float] = None

    def __post_init__(self) -> None:
        cfg = self.cfg
        if cfg.family in ("audio", "vlm"):
            # audio needs encoder frames, vlm per-request image embeddings —
            # neither fits the token-prompt Request; serving them here would
            # silently drop the non-token inputs.
            raise NotImplementedError(
                f"ContinuousEngine serves token-prompt LM families; use "
                f"ServeEngine for {cfg.family}"
            )
        if cfg.moe is not None and not cfg.moe.dropless:
            # Token-choice capacity dropping routes by whole-batch content:
            # one request's load would change another's outputs. Dropless
            # routing is per-token, keeping slots independent.
            warnings.warn(
                "continuous batching with capacity-dropping MoE couples "
                "requests through the router; set moe.dropless for "
                "request-isolated serving",
                RuntimeWarning,
                stacklevel=2,
            )

        # Resolve the SLO knobs (fields beat env).
        if self.slo_ttft_ms is None:
            env = os.environ.get("REPRO_SLO_TTFT_MS", "")
            self.slo_ttft_ms = float(env) if env else None
        if self.slo_itl_ms is None:
            env = os.environ.get("REPRO_SLO_ITL_MS", "")
            self.slo_itl_ms = float(env) if env else None

        # Resolve the prompt-side feature knobs (fields beat env).
        if self.prefill_chunk is None:
            env = os.environ.get("REPRO_PREFILL_CHUNK", "")
            self.prefill_chunk = int(env) if env else None
        if self.prefix_cache is None:
            env = os.environ.get("REPRO_PREFIX_CACHE", "")
            self.prefix_cache = env.lower() not in ("", "0", "false", "no")
        if self.prefix_cache and self.prefill_chunk is None:
            self.prefill_chunk = 32  # suffix prefill rides the chunk entry
        if self.prefill_chunk is not None:
            w = self.prefill_chunk
            if w < 1 or (w & (w - 1)):
                raise ValueError(f"prefill_chunk must be a power of two, got {w}")
            attn_only = all(
                bd.mixer in ("attn", "attn_local", "none") for bd in cfg.pattern
            )
            if not attn_only:
                # Recurrent state can't resume mid-prompt from a scatter;
                # fall back to monolithic bucket prefill rather than fail.
                warnings.warn(
                    "chunked prefill / prefix cache need attention-only "
                    f"patterns; disabled for {cfg.name}",
                    RuntimeWarning,
                    stacklevel=2,
                )
                self.prefill_chunk = None
                self.prefix_cache = False
        self._trie: Optional[PrefixCache] = (
            PrefixCache(
                block_size=self.prefix_block,
                capacity_tokens=self.prefix_capacity,
                kv_format=self.kv_format,
                n_kv=cfg.n_kv,
            )
            if self.prefix_cache
            else None
        )
        self._pending: List[_PendingJoin] = []
        # Prefix-trie residency high-watermark (tokens) — the trie persists
        # across serve() calls, so the peak does too.
        self._prefix_hwm = 0

        # The step programs' names are part of the profile's vocabulary:
        # a trace shows them as ``jit_engine_prefill``, ``jit_engine_chunk``
        # and ``jit_engine_decode``, and bench/lib/trace.py tells the three
        # kinds apart by ``_prefill``, ``_chunk`` and ``_decode`` in them.
        # No other program the engine dispatches carries those tokens.
        @jax.jit
        def engine_prefill(params, tokens, lengths):
            logits, caches = model_api.prefill_bucketed(
                cfg, params, tokens, lengths, self.cache_dtype
            )
            return logits, caches

        @functools.partial(jax.jit, donate_argnums=(1,))
        def engine_chunk(params, caches, ctoks, offsets, last_idx):
            return model_api.prefill_chunk(
                cfg, params, ctoks, caches, offsets, last_idx
            )

        @functools.partial(jax.jit, donate_argnums=(1,))
        def engine_decode(params, caches, tok, pos, active, key):
            logits, caches = model_api.decode_at(cfg, params, tok, caches, pos)
            nxt = sample_token(logits, key, self.temperature)
            # Masked slots cost a lane, not a recompile: they hold token and
            # position so the step's shapes/program never change.
            nxt = jnp.where(active[:, None], nxt, tok)
            pos = pos + active.astype(jnp.int32)
            return nxt, caches, pos

        self._prefill = engine_prefill
        self._chunk = engine_chunk
        self._decode = engine_decode
        # Utilization-attribution state (obs.attr): the GEMM workload of each
        # compiled step, captured once at trace time, then charged with every
        # subsequent dispatch's measured wall time. Keyed per compiled
        # program: one decode step; chunk steps per (rows, bucket, width).
        # Monolithic prefills are not charged: the host sees only their
        # async enqueue, not their execution.
        self._prefill_workloads: Dict[tuple, dict] = {}

    # -- introspection -----------------------------------------------------

    def decode_compilations(self) -> Optional[int]:
        """Number of compiled decode programs (None if jax hides the cache)."""
        try:
            return int(self._decode._cache_size())
        except Exception:
            return None

    def prefix_cache_stats(self) -> Optional[Dict[str, int]]:
        """Hit/miss/eviction/residency counters of the prefix trie (None
        when the cache is disabled)."""
        if self._trie is None:
            return None
        return {
            "hits": self._trie.hits,
            "misses": self._trie.misses,
            "evictions": self._trie.evictions,
            "cached_tokens": self._trie.cached_tokens,
        }

    # -- utilization attribution -------------------------------------------

    def _step_workload(self, store_key, fn, args, step_recs, kind: str):
        """Resolve the GEMM workload to charge for one dispatch.

        Records present => this dispatch traced: store its workload, return
        None (the tick's wall bracket includes trace + compile — skip it).
        Records absent and the key unknown (the compile happened while
        metrics were disabled) => re-capture at zero cost via
        ``jax.eval_shape`` so timed dispatches stop silently contributing
        zero attributed GEMM-seconds; each re-capture counts on
        ``gemm.attr_fallback``.
        """
        if step_recs:
            self._prefill_workloads[store_key] = _attr.aggregate(step_recs)
            return None
        wl = self._prefill_workloads.get(store_key)
        if wl is None and _obs.enabled():
            # jax's trace cache is keyed on the function object + avals and
            # is shared with the jit wrapper's original (metrics-off) trace,
            # so eval_shape of either the wrapper or its unjitted body would
            # hit the cache and emit nothing. A fresh lambda is a fresh
            # cache key: the body genuinely re-traces (abstractly — zero
            # FLOPs, no compile) and the registry records re-fire.
            inner = getattr(fn, "__wrapped__", fn)
            with _attr.capture_gemms() as recs:
                jax.eval_shape(lambda *a: inner(*a), *args)
            if recs:
                wl = _attr.aggregate(recs)
                self._prefill_workloads[store_key] = wl
                _obs.counter("gemm.attr_fallback", step=kind).inc()
        return wl

    # -- serving -----------------------------------------------------------

    def serve(
        self,
        requests: List[Request],
        *,
        key: Optional[jax.Array] = None,
        on_token: Optional[Callable[[int, int], None]] = None,
        max_steps: Optional[int] = None,
    ) -> ServingReport:
        """Run ``requests`` to completion; returns outputs + counters.

        ``on_token(rid, token)`` streams every sampled token as soon as the
        host sees it (one fused step behind the device).
        """
        for r in requests:
            if len(r.prompt) + r.max_new_tokens > self.max_len:
                raise ValueError(
                    f"request {r.rid}: prompt {len(r.prompt)} + "
                    f"max_new {r.max_new_tokens} exceeds max_len {self.max_len}"
                )
        key = key if key is not None else jax.random.key(0)
        sched = Scheduler(
            self.cfg,
            eos_id=self.eos_id,
            exact_buckets=self.exact_buckets,
            max_bucket=self.max_len,
        )
        for r in requests:
            sched.submit(r)
        pool = SlotPool.create(
            self.cfg, self.n_slots, self.max_len, self.cache_dtype,
            kv_format=self.kv_format,
        )
        self._last_kv_bytes_per_slot = pool.kv_bytes_per_slot()
        # Abandoned pipelines die with their serve() call (slot leases are
        # per-pool); the prefix trie deliberately survives — warmup runs
        # populate it for the timed runs that follow.
        self._pending = []

        b = self.n_slots
        tok = jnp.zeros((b, 1), jnp.int32)
        pos = jnp.zeros((b,), jnp.int32)
        active = [False] * b  # host truth; device mask derived on change
        active_dev = jnp.asarray(active)

        # Without EOS eviction or a streaming callback, retirement depends
        # only on token *counts* — so the loop never reads token values and
        # decode dispatches pipeline freely; values are fetched once at the
        # end (deferred detokenization). With EOS/streaming, every step
        # syncs on the sampled tokens.
        sync = on_token is not None or self.eos_id is not None
        pending = []  # (device tokens [*, 1], [(row, rid), ...]) per step

        # Lifecycle wall stamps (always kept — the report's percentile fields
        # are product, not telemetry; only the obs emission is gated). A
        # request's clock starts when the loop reaches its arrival tick.
        wall = time.perf_counter
        by_arrival = sorted(requests, key=lambda r: r.arrival)
        n_arrival_stamped = 0
        lc = _Lifecycle()

        step = 0
        decode_steps = 0
        prefill_batches = 0
        generated = 0
        occupancy_acc = 0.0
        limit = max_steps if max_steps is not None else (
            sum(r.arrival + r.max_new_tokens for r in requests) + 10 * self.max_len
            # chunked joins spend up to ceil(plen/W) extra ticks per request
            + (sum(len(r.prompt) for r in requests) if self.prefill_chunk else 0)
        )

        while not (sched.drained and pool.n_active == 0):
            if step > limit:
                raise RuntimeError(f"serving did not drain within {limit} steps")
            while (
                n_arrival_stamped < len(by_arrival)
                and by_arrival[n_arrival_stamped].arrival <= step
            ):
                lc.arrival(by_arrival[n_arrival_stamped], wall())
                n_arrival_stamped += 1

            # -- join: refill free slots from the queue ---------------------
            chunked = self.prefill_chunk is not None
            while pool.n_free and len(sched):
                admissible = None
                if chunked and len(self._pending) >= _MAX_PENDING:
                    # Pipeline full: only prompts whose remaining prefill
                    # fits one chunk can still join (they complete inline,
                    # no pipeline slot). Everything else waits — and the
                    # scheduler's deepest-admissible-bucket fallback keeps
                    # short arrivals flowing past the blocked head.
                    admissible = (
                        lambda r: self._suffix_len(r) <= self.prefill_chunk
                    )
                with _obs.span("serve.admit"):
                    batch = sched.next_batch(
                        pool.n_free, now=step, admissible=admissible
                    )
                    if batch:
                        adm = sched.last_admission or {}
                        lc.admitted(
                            batch, wall(), adm.get("bucket"),
                            bool(adm.get("fallthrough")),
                            phase="prefix_attach" if chunked else "prefill",
                        )
                        if chunked:
                            pj = self._begin_join(sched, pool, batch, step)
                            lc.attached(batch, wall())
                if not batch:
                    break
                if self.temperature > 0:
                    key, sub = jax.random.split(key)
                else:
                    sub = key  # greedy: sampling ignores the key
                if chunked:
                    if len(self._pending) < _MAX_PENDING:
                        self._pending.append(pj)  # advances below, this tick
                    else:
                        # All-fast batch (admissible guaranteed it): one
                        # chunk finishes the whole prompt set — join now.
                        # (Loop, not a single advance: a trie eviction racing
                        # the admissibility check can lengthen a suffix.)
                        with _obs.span(
                            "serve.chunk",
                            args=_join_args(pj.batch, pj.rows, pj.lb),
                        ):
                            while not pj.all_done:
                                self._advance_chunk(pj)
                            tok, pos, n_gen = self._complete_join(
                                pj, sched, pool, tok, pos, active, sub, step,
                                on_token, sync, pending,
                            )
                            self._stamp_join(pj.batch, sched, wall, lc)
                            active_dev = jnp.asarray(active)
                        prefill_batches += 1
                        generated += n_gen
                else:
                    with _obs.span(
                        "serve.prefill",
                        args=_join_args(
                            batch, _pow2(len(batch)), adm["bucket"]
                        ),
                    ):
                        tok, pos, active, n_gen = self._join(
                            sched, pool, batch, tok, pos, active, sub, step,
                            on_token, sync, pending,
                        )
                        # First token exists now (sampled from prefill
                        # logits): the join stamp closes each request's TTFT
                        # window.
                        self._stamp_join(batch, sched, wall, lc)
                        active_dev = jnp.asarray(active)
                    prefill_batches += 1
                    generated += n_gen  # one token per request, prefill logits

            # -- advance the pending chunk pipeline by one chunk ------------
            if self._pending:
                pj = self._pending[0]
                with _obs.span(
                    "serve.chunk", args=_join_args(pj.batch, pj.rows, pj.lb)
                ):
                    self._advance_chunk(pj)
                    if pj.all_done:
                        if self.temperature > 0:
                            key, sub = jax.random.split(key)
                        else:
                            sub = key
                        tok, pos, n_gen = self._complete_join(
                            pj, sched, pool, tok, pos, active, sub, step,
                            on_token, sync, pending,
                        )
                        self._stamp_join(pj.batch, sched, wall, lc)
                        active_dev = jnp.asarray(active)
                if pj.all_done:
                    prefill_batches += 1
                    generated += n_gen
                    self._pending.pop(0)

            if not any(active):
                if sched.drained and not self._pending:
                    break
                step += 1  # idle tick: next arrival / next pending chunk
                continue

            # -- decode: one fused masked step over the whole pool ----------
            t_step = wall()
            n_live = sum(active)
            with _obs.span("serve.decode"):
                if self.temperature > 0:
                    key, sub = jax.random.split(key)
                else:
                    sub = key
                with _attr.capture_gemms() as step_recs:
                    tok, pool.caches, pos = self._decode(
                        self.params, pool.caches, tok, pos, active_dev, sub
                    )
                decode_wl = self._step_workload(
                    ("decode",), self._decode,
                    (self.params, pool.caches, tok, pos, active_dev, sub),
                    step_recs, "decode",
                )
            decode_steps += 1
            occupancy_acc += n_live / self.n_slots
            step += 1

            # -- evict: stream tokens, retire finished requests -------------
            # Guard against already-retired lanes: a slot released earlier in
            # this tick (one-token request at join) must not be swept again —
            # the owner check plus the release return value make the sweep a
            # no-op for such lanes instead of freeing a re-leased slot twice.
            live = [
                s for s in pool.active_slots()
                if active[s] and pool.owner_of(s) is not None
            ]
            live_rids = [pool.owner_of(s) for s in live]
            n_retired = 0
            retired_now: List[tuple] = []  # (Request, reason)
            changed = False
            if sync:
                with _obs.span("serve.readback"):
                    emitted = np.asarray(tok[:, 0])
                with _obs.span("serve.emit"):
                    for slot, rid in zip(live, live_rids):
                        t = int(emitted[slot])
                        if on_token is not None:
                            on_token(rid, t)
                        generated += 1
                        if sched.record_token(rid, t, now=step):
                            reason = (
                                "eos"
                                if self.eos_id is not None
                                and t == self.eos_id
                                else "budget"
                            )
                            retired_now.append(
                                (sched.states[rid].request, reason)
                            )
                            if pool.release(slot):
                                n_retired += 1
                            active[slot] = False
                            changed = True
                    if changed:
                        active_dev = jnp.asarray(active)
            else:
                pending.append((tok, list(zip(live, live_rids))))
                for slot, rid in zip(live, live_rids):
                    generated += 1
                    if sched.record_emitted(rid, now=step):
                        retired_now.append(
                            (sched.states[rid].request, "budget")
                        )
                        if pool.release(slot):
                            n_retired += 1
                        active[slot] = False
                        changed = True
                if changed:
                    active_dev = jnp.asarray(active)

            # Per-tick telemetry: step wall time, each live lane's
            # inter-token gap, queue/occupancy gauges. Retirement stamps
            # come after the token stamps so a request's last ITL instant
            # lands inside its span.
            now = wall()
            with _obs.span("serve.telemetry"):
                _obs.histogram("serve.step_seconds").observe(now - t_step)
                if decode_wl:
                    # Same host-wall caveat as ITL: on the deferred path this
                    # is dispatch cadence, on the sync path token-to-token
                    # time.
                    _attr.observe_step(decode_wl, now - t_step)
                for rid in live_rids:
                    lc.token(sched.states[rid].request, now)
                for r, reason in retired_now:
                    lc.retired(r, sched.states[r.rid], reason, now)
                _obs.counter("serve.tokens").inc(len(live_rids))
                if n_retired:
                    _obs.counter("serve.requests", event="retired").inc(
                        n_retired
                    )
                _obs.gauge("serve.queue_depth").set(sched.n_arrived(step))
                _obs.gauge("serve.occupancy").set(n_live / self.n_slots)
                _obs.gauge("serve.slot_pool_hwm").set(pool.leased_hwm)

        # Deferred fetch: one host sync for the whole run.
        for arr, pairs in pending:
            vals = np.asarray(arr[:, 0])
            for row, rid in pairs:
                sched.states[rid].tokens.append(int(vals[row]))
        jax.block_until_ready(tok)
        outputs = {rid: st.tokens for rid, st in sched.states.items()}
        _obs.gauge("serve.slot_pool_hwm").set(pool.leased_hwm)
        goodput = lc.goodput(
            requests,
            None if self.slo_ttft_ms is None else self.slo_ttft_ms / 1e3,
            None if self.slo_itl_ms is None else self.slo_itl_ms / 1e3,
        )
        report = ServingReport(
            outputs=outputs,
            generated_tokens=generated,
            decode_steps=decode_steps,
            prefill_batches=prefill_batches,
            mean_occupancy=(occupancy_acc / decode_steps) if decode_steps else 0.0,
            wall_time_s=0.0,  # stamped by timed_serve
            kv_bytes_per_slot=self._last_kv_bytes_per_slot,
            ttft_p50=_obs.percentile(lc.ttfts, 50),
            ttft_p99=_obs.percentile(lc.ttfts, 99),
            itl_p50=_obs.percentile(lc.itls, 50),
            itl_p99=_obs.percentile(lc.itls, 99),
            goodput=goodput,
            queue_p50=_obs.percentile(lc.queue_s, 50),
            queue_p99=_obs.percentile(lc.queue_s, 99),
            attach_p50=_obs.percentile(lc.attach_s, 50),
            attach_p99=_obs.percentile(lc.attach_s, 99),
            chunk_prefill_p50=_obs.percentile(lc.chunk_s, 50),
            chunk_prefill_p99=_obs.percentile(lc.chunk_s, 99),
            slot_hwm=pool.leased_hwm,
        )
        _obs.event(
            "serving_report",
            requests=len(requests),
            generated_tokens=report.generated_tokens,
            decode_steps=report.decode_steps,
            mean_occupancy=report.mean_occupancy,
            ttft_p50=report.ttft_p50,
            ttft_p99=report.ttft_p99,
            itl_p50=report.itl_p50,
            itl_p99=report.itl_p99,
            goodput=report.goodput,
            queue_p50=report.queue_p50,
            queue_p99=report.queue_p99,
            slot_hwm=report.slot_hwm,
        )
        return report

    def timed_serve(self, requests: List[Request], **kw) -> ServingReport:
        t0 = time.perf_counter()
        report = self.serve(requests, **kw)
        report.wall_time_s = time.perf_counter() - t0
        return report

    # -- internals ---------------------------------------------------------

    def _stamp_join(self, batch, sched, wall, lc: _Lifecycle) -> None:
        """Close each admitted request's TTFT window (its first token was
        just sampled) and emit the admission counters. One shared ``now``
        per batch simultaneously closes the last pre-decode phase and
        timestamps the first token — the reason the exported phase chain
        sums exactly to the TTFT sample."""
        lc.first_token(
            batch, sched, self.eos_id, wall(),
            chunked=self.prefill_chunk is not None,
        )

    def _attach_len(self, matched: int, plen: int) -> int:
        """Usable prefix span: snap the trie match down to a chunk boundary
        (resume offsets stay chunk-aligned — one partial chunk per prompt,
        at the tail) and always leave >= 1 token to prefill."""
        w = self.prefill_chunk
        attach = (min(matched, plen - 1) // w) * w
        return max(attach, 0)

    def _suffix_len(self, r: Request) -> int:
        """Prompt tokens left to prefill after a (hypothetical) prefix
        attach — the admissibility measure for a full chunk pipeline."""
        if self._trie is None:
            return len(r.prompt)
        _, matched = self._trie.match(r.prompt)
        return len(r.prompt) - self._attach_len(matched, len(r.prompt))

    def _begin_join(
        self, sched: Scheduler, pool: SlotPool, batch: List[Request], step: int
    ) -> _PendingJoin:
        """Lease slots, build standalone caches, attach cached prefixes.

        The returned pipeline advances one chunk per engine tick; the batch
        joins the pool (and its lanes activate) only at completion.
        """
        lb = sched.bucket(max(len(r.prompt) for r in batch))
        rows = _pow2(len(batch))
        plens = np.array([len(r.prompt) for r in batch], np.int64)
        caches = model_api.init_state(
            self.cfg, rows, lb, self.cache_dtype
        )
        offsets = np.zeros(len(batch), np.int64)
        nodes: List[list] = [[] for _ in batch]
        floors = None
        if self._trie is not None:
            floors_np = None
            for i, r in enumerate(batch):
                path, matched = self._trie.match(r.prompt)
                attach = self._attach_len(matched, int(plens[i]))
                if attach <= 0:
                    self._trie.misses += 1
                    _obs.counter("serve.prefix_cache.misses").inc()
                    _tracing.instant(
                        r.uid, "prefix_miss", time.perf_counter(),
                        matched=int(matched),
                    )
                    continue
                self._trie.hits += 1
                _obs.counter("serve.prefix_cache.hits").inc()
                # Keep only the nodes the attach actually covers resident.
                n_nodes = -(-attach // self._trie.block_size)  # ceil
                nodes[i] = path[:n_nodes]
                self._trie.acquire(nodes[i])
                spans, fls = self._trie.gather(nodes[i])
                caches = _attach_prefix(caches, spans, i, attach)
                _tracing.instant(
                    r.uid, "prefix_attach", time.perf_counter(),
                    tokens=int(attach), matched=int(matched),
                    spans=int(n_nodes),
                )
                _tracing.annotate(
                    r.uid, prefix_tokens=int(attach), prefix_spans=int(n_nodes)
                )
                if fls is not None:
                    if floors_np is None:
                        floors_np = _zero_floors(rows, fls)
                    for e, f in enumerate(fls):
                        if f is not None:
                            floors_np[e][0][:, i] = np.asarray(f[0])
                            floors_np[e][1][:, i] = np.asarray(f[1])
                offsets[i] = attach
            if floors_np is not None:
                floors = tuple(
                    None if f is None else (jnp.asarray(f[0]), jnp.asarray(f[1]))
                    for f in floors_np
                )
        slots = pool.allocate([r.rid for r in batch])
        sched.admit(batch, slots, now=step)
        for r, s in zip(batch, slots):
            _tracing.set_slot(r.uid, s)
        return _PendingJoin(
            batch=batch, slots=slots, caches=caches, rows=rows, lb=lb,
            offsets=offsets, plens=plens, nodes=nodes, floors=floors,
        )

    def _advance_chunk(self, pj: _PendingJoin) -> None:
        """Advance every unfinished row of ``pj`` by one prompt chunk."""
        w = self.prefill_chunk
        ctoks = np.zeros((pj.rows, w), np.int32)
        # Sentinel offset = bucket length: every K/V write of that row drops
        # and its (garbage) logits row is never selected.
        offs = np.full((pj.rows,), pj.lb, np.int32)
        last_idx = np.zeros((pj.rows,), np.int32)
        fin = np.zeros((pj.rows,), bool)
        advanced = []  # (uid, off, end): trace slices stamped post-dispatch
        for i, r in enumerate(pj.batch):
            if pj.done[i]:
                continue
            off = int(pj.offsets[i])
            end = min(off + w, int(pj.plens[i]))
            offs[i] = off
            ctoks[i, : end - off] = np.asarray(r.prompt[off:end], np.int32)
            if end >= pj.plens[i]:
                fin[i] = True
                last_idx[i] = int(pj.plens[i]) - 1 - off
            pj.offsets[i] = end
            advanced.append((r.uid, off, end))
        args = (
            self.params, pj.caches, jnp.asarray(ctoks), jnp.asarray(offs),
            jnp.asarray(last_idx),
        )
        t_ck = time.perf_counter()
        with _attr.capture_gemms() as ck_recs:
            logits, pj.caches = self._chunk(*args)
        t_done = time.perf_counter()
        wl = self._step_workload(
            (pj.rows, pj.lb, w), self._chunk,
            (self.params, pj.caches) + args[2:], ck_recs, "chunk",
        )
        if wl:
            _attr.observe_step(wl, t_done - t_ck)
        # One nested slice per row advanced this tick (host dispatch
        # bracket — the chunk step itself is async like every dispatch).
        for uid, off, end in advanced:
            _tracing.slice_event(uid, "chunk", t_ck, t_done, offset=off, end=end)
        fin_dev = jnp.asarray(fin)
        pj.first_logits = (
            logits if pj.first_logits is None
            else jnp.where(fin_dev[:, None], logits, pj.first_logits)
        )
        pj.done |= fin[: len(pj.batch)]

    def _complete_join(
        self, pj: _PendingJoin, sched: Scheduler, pool: SlotPool,
        tok, pos, active, key, step, on_token, sync, pending,
    ):
        """All prompts consumed: join the pool, seed lanes, sample first
        tokens, insert the finished prompts into the prefix trie."""
        first = sample_token(pj.first_logits, key, self.temperature)
        pool.join(pj.caches, pj.slots, pj.floors)
        slot_idx = jnp.asarray(pj.slots, jnp.int32)
        tok = tok.at[slot_idx].set(first[: len(pj.batch)])
        pos = pos.at[slot_idx].set(
            jnp.asarray(pj.plens[: len(pj.batch)], jnp.int32)
        )
        n_gen = len(pj.batch)
        if sync:
            first_host = np.asarray(first[:, 0])
            for i, r in enumerate(pj.batch):
                t = int(first_host[i])
                if on_token is not None:
                    on_token(r.rid, t)
                if sched.record_token(r.rid, t, now=step):
                    pool.release(pj.slots[i])  # one-token request
                else:
                    active[pj.slots[i]] = True
        else:
            pending.append((first, [(i, r.rid) for i, r in enumerate(pj.batch)]))
            for i, r in enumerate(pj.batch):
                if sched.record_emitted(r.rid, now=step):
                    pool.release(pj.slots[i])
                else:
                    active[pj.slots[i]] = True
        if self._trie is not None:
            ev0 = self._trie.evictions
            for i, r in enumerate(pj.batch):
                self._trie.insert(r.prompt, int(pj.plens[i]), pj.caches, i)
                if pj.nodes[i]:
                    self._trie.release(pj.nodes[i])
            if self._trie.evictions > ev0:
                _obs.counter("serve.prefix_cache.evictions").inc(
                    self._trie.evictions - ev0
                )
            _obs.gauge("serve.prefix_cache.cached_tokens").set(
                self._trie.cached_tokens
            )
            self._prefix_hwm = max(self._prefix_hwm, self._trie.cached_tokens)
            _obs.gauge("serve.prefix_cache.hwm_tokens").set(self._prefix_hwm)
        return tok, pos, n_gen

    def _join(
        self,
        sched: Scheduler,
        pool: SlotPool,
        batch: List[Request],
        tok: jax.Array,
        pos: jax.Array,
        active: List[bool],
        key: jax.Array,
        step: int,
        on_token,
        sync: bool,
        pending,
    ):
        """Prefill one bucket, scatter it into leased slots, seed the lanes."""
        lb = sched.bucket(max(len(r.prompt) for r in batch))
        # Filler rows (up to the power of two) duplicate row 0 and
        # scatter-drop.
        rows = _pow2(len(batch))
        tokens = np.zeros((rows, lb), np.int32)
        lengths = np.ones((rows,), np.int32)
        for i, r in enumerate(batch):
            tokens[i, : len(r.prompt)] = np.asarray(r.prompt, np.int32)
            lengths[i] = len(r.prompt)
        if rows > len(batch):
            tokens[len(batch):] = tokens[0]
            lengths[len(batch):] = lengths[0]

        logits, caches = self._prefill(
            self.params, jnp.asarray(tokens), jnp.asarray(lengths)
        )
        first = sample_token(logits, key, self.temperature)

        slots = pool.allocate([r.rid for r in batch])
        sched.admit(batch, slots, now=step)
        for r, s in zip(batch, slots):
            _tracing.set_slot(r.uid, s)
        pool.join(caches, slots)

        slot_idx = jnp.asarray(slots, jnp.int32)
        tok = tok.at[slot_idx].set(first[: len(batch)])
        pos = pos.at[slot_idx].set(jnp.asarray(lengths[: len(batch)]))
        n_gen = len(batch)
        if sync:
            first_host = np.asarray(first[:, 0])
            for i, r in enumerate(batch):
                t = int(first_host[i])
                if on_token is not None:
                    on_token(r.rid, t)
                if sched.record_token(r.rid, t, now=step):
                    pool.release(slots[i])  # one-token request: retire at join
                else:
                    active[slots[i]] = True
        else:
            pending.append((first, [(i, r.rid) for i, r in enumerate(batch)]))
            for i, r in enumerate(batch):
                if sched.record_emitted(r.rid, now=step):
                    pool.release(slots[i])
                else:
                    active[slots[i]] = True
        return tok, pos, active, n_gen


def _pow2(n: int) -> int:
    """Rows of a join's prefill: ``n`` rounded up to a power of two, so the
    prefill and chunk programs compile a bounded number of times per
    bucket."""
    rows = 1
    while rows < n:
        rows *= 2
    return rows


def _join_args(batch: List[Request], rows: int, bucket) -> Dict[str, int]:
    """Profiler-annotation arguments of one join's span."""
    return {
        "requests": len(batch), "rows": rows, "bucket": int(bucket),
        "tokens": sum(len(r.prompt) for r in batch),
    }


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def _attach_prefix_jit(caches, spans, row, attach: int):
    out = []
    for c, sp in zip(caches, spans):
        if sp is None or not hasattr(c, "k"):
            out.append(c)
            continue
        k, v = sp
        out.append(
            c._replace(
                k=jax.lax.dynamic_update_slice(
                    c.k,
                    k[:, :attach].astype(c.k.dtype)[:, None],
                    (0, row, 0, 0),
                ),
                v=jax.lax.dynamic_update_slice(
                    c.v,
                    v[:, :attach].astype(c.v.dtype)[:, None],
                    (0, row, 0, 0),
                ),
            )
        )
    return tuple(out)


def _attach_prefix(caches, spans, row: int, attach: int):
    """Write a gathered prefix span into one row of standalone prefill
    caches: positions ``[0:attach]`` of every attention entry. The span may
    run past ``attach`` (the trie matched beyond the chunk-aligned snap) —
    the excess is simply not attached.

    Donated jit: the standalone stack is freshly initialized and threaded
    through repeated attaches, so XLA updates it in place instead of copying
    the whole pool-sized buffer per row. ``row`` is traced (one program
    serves every lane); compile shapes key on the span/attach bucket, like
    the chunk-prefill programs — the decode step is untouched."""
    return _attach_prefix_jit(caches, spans, jnp.int32(row), int(attach))


def _zero_floors(rows: int, fls):
    """Host-side zero scale floors, per entry ``[n_periods, rows, n_kv]`` —
    rows that attach a quantized prefix overwrite their lane."""
    out = []
    for f in fls:
        if f is None:
            out.append(None)
        else:
            p, n_kv = np.asarray(f[0]).shape
            out.append(
                (
                    np.zeros((p, rows, n_kv), np.float32),
                    np.zeros((p, rows, n_kv), np.float32),
                )
            )
    return out
