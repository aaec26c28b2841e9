"""repro.obs: registry semantics, exporters, spans, events — and the
instrumented layers (kernels, serving, training) emitting through them.

The load-bearing claim is the last test class: ALL instrumentation is
host-side Python (executed at trace time inside ``jit``), so the compiled
decode-step HLO carries an identical instruction census whether telemetry
is on or off — ``REPRO_METRICS=0`` provably costs zero device work because
``REPRO_METRICS=1`` already does.
"""

import collections
import json
import re
import warnings

import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

from repro import obs
from repro.kernels import ops

# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestCounters:
    def test_inc_and_labels(self):
        obs.counter("t.calls", backend="xla").inc()
        obs.counter("t.calls", backend="xla").inc(2)
        obs.counter("t.calls", backend="pallas").inc()
        snap = obs.snapshot()
        assert snap["counters"]["t.calls"]["backend=xla"] == 3.0
        assert snap["counters"]["t.calls"]["backend=pallas"] == 1.0

    def test_counters_only_go_up(self):
        with pytest.raises(ValueError):
            obs.counter("t.calls").inc(-1)

    def test_label_order_is_canonical(self):
        obs.counter("t.c", b="2", a="1").inc()
        obs.counter("t.c", a="1", b="2").inc()
        snap = obs.snapshot()
        assert snap["counters"]["t.c"] == {"a=1,b=2": 2.0}

    def test_metric_name_is_positional_only(self):
        # a label literally called "name" must not collide with the metric
        # name parameter (spans label their histogram by span name)
        obs.counter("t.named", name="x").inc()
        assert obs.snapshot()["counters"]["t.named"]["name=x"] == 1.0


class TestGauges:
    def test_set_and_add(self):
        obs.gauge("t.g").set(4.0)
        obs.gauge("t.g").add(-1.5)
        assert obs.snapshot()["gauges"]["t.g"][""] == 2.5


class TestHistograms:
    def test_summary_stats(self):
        h = obs.histogram("t.h")
        for v in (0.1, 0.2, 0.3, 0.4):
            h.observe(v)
        s = obs.snapshot()["histograms"]["t.h"][""]
        assert s["count"] == 4
        assert s["sum"] == pytest.approx(1.0)
        assert s["mean"] == pytest.approx(0.25)
        assert s["min"] == pytest.approx(0.1)
        assert s["max"] == pytest.approx(0.4)

    def test_cumulative_buckets(self):
        h = obs.histogram("t.b", buckets=[1.0, 2.0, 4.0])
        for v in (0.5, 1.5, 3.0, 100.0):
            h.observe(v)
        s = obs.snapshot()["histograms"]["t.b"][""]
        # snapshot buckets are cumulative counts per le-edge (+Inf last)
        assert s["buckets"] == {"1.0": 1, "2.0": 2, "4.0": 3, "+Inf": 4}

    def test_percentile_linear_interpolation(self):
        # no samples is "no answer", not "0.0 latency"
        assert obs.percentile([], 50) is None
        assert obs.percentile([3.0], 99) == 3.0
        assert obs.percentile([1.0, 2.0, 3.0, 4.0], 50) == pytest.approx(2.5)
        assert obs.percentile([1.0, 2.0, 3.0, 4.0], 100) == 4.0
        xs = list(range(101))
        assert obs.percentile(xs, 99) == pytest.approx(99.0)

    def test_reset_drops_everything(self):
        obs.counter("t.c").inc()
        obs.histogram("t.h").observe(1.0)
        obs.reset()
        snap = obs.snapshot()
        assert snap["counters"] == {} and snap["histograms"] == {}


class TestExporters:
    def test_to_json_roundtrips(self):
        obs.counter("t.c", x="1").inc()
        assert json.loads(obs.to_json())["counters"]["t.c"]["x=1"] == 1.0

    def test_prometheus_text(self):
        obs.counter("gemm.calls", backend="xla").inc(2)
        obs.gauge("serve.occupancy").set(0.5)
        obs.histogram("t.h").observe(0.3)
        text = obs.prometheus_text()
        assert 'repro_gemm_calls_total{backend="xla"} 2.0' in text
        assert "repro_serve_occupancy 0.5" in text
        assert "repro_t_h_count 1" in text
        assert 'repro_t_h_bucket{le="+Inf"} 1' in text

    def test_prometheus_from_file_snapshot(self):
        # the CLI renders snapshots other processes dumped: exporter must
        # work from a plain dict, not just the live registry
        obs.counter("t.c").inc()
        snap = json.loads(json.dumps(obs.snapshot()))
        obs.reset()
        assert "repro_t_c_total 1.0" in obs.prometheus_text(snap)


class TestDisabled:
    def test_disabled_fetches_are_null(self):
        prev = obs.set_enabled(False)
        try:
            c = obs.counter("t.off")
            c.inc(5)
            obs.histogram("t.off.h").observe(1.0)
            assert obs.snapshot()["counters"] == {}
        finally:
            obs.set_enabled(prev)

    def test_disabled_span_and_event_are_noops(self):
        prev = obs.set_enabled(False)
        try:
            with obs.span("t.span"):
                pass
            obs.event("t.kind", x=1)
            assert obs.snapshot()["histograms"] == {}
            assert obs.recent_events(10, kind="t.kind") == []
        finally:
            obs.set_enabled(prev)


# ---------------------------------------------------------------------------
# spans, logger, events
# ---------------------------------------------------------------------------


class TestSpans:
    def test_span_records_wall_time(self):
        with obs.span("t.block", phase="x"):
            pass
        s = obs.snapshot()["histograms"]["span.seconds"]["name=t.block,phase=x"]
        assert s["count"] == 1 and s["max"] >= 0.0

    def test_span_args_annotate_the_profile_not_the_histogram(self, tmp_path):
        import glob

        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.span("t.args", args={"rows": 3}, phase="y"):
                pass
        finally:
            jax.profiler.stop_trace()
        assert list(obs.snapshot()["histograms"]["span.seconds"]) == [
            "name=t.args,phase=y"
        ]
        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        pd = jax.profiler.ProfileData.from_file(path[0])
        stats = [
            dict(e.stats) for p in pd.planes for line in p.lines
            for e in line.events if e.name == "t.args"
        ]
        assert len(stats) == 1
        assert stats[0]["rows"] == 3 and stats[0]["phase"] == "y"

    def test_span_propagates_exceptions_but_still_records(self):
        with pytest.raises(RuntimeError):
            with obs.span("t.boom"):
                raise RuntimeError("boom")
        assert obs.snapshot()["histograms"]["span.seconds"]["name=t.boom"][
            "count"
        ] == 1


class TestLogger:
    def test_text_mode(self, capsys, monkeypatch):
        monkeypatch.delenv("REPRO_LOG", raising=False)
        obs.get_logger("serve").info("generated", tokens=128, wall_s=1.25)
        out = capsys.readouterr().out
        assert out == "[serve] generated tokens=128 wall_s=1.25\n"

    def test_json_mode(self, capsys, monkeypatch):
        monkeypatch.setenv("REPRO_LOG", "json")
        obs.get_logger("serve").info("generated", tokens=128)
        rec = json.loads(capsys.readouterr().out)
        assert rec["component"] == "serve"
        assert rec["event"] == "generated" and rec["tokens"] == 128

    def test_raw_passthrough_and_json_wrap(self, capsys, monkeypatch):
        monkeypatch.delenv("REPRO_LOG", raising=False)
        obs.get_logger("tune").raw("wrote 2 entries -> /tmp/t.json")
        assert capsys.readouterr().out == "wrote 2 entries -> /tmp/t.json\n"
        monkeypatch.setenv("REPRO_LOG", "json")
        obs.get_logger("tune").raw("hello world")
        assert json.loads(capsys.readouterr().out)["msg"] == "hello world"


class TestEvents:
    def test_ring_buffer_and_kind_filter(self):
        obs.event("a", i=1)
        obs.event("b", i=2)
        obs.event("a", i=3)
        evts = obs.recent_events(10, kind="a")
        assert [e["i"] for e in evts] == [1, 3]

    def test_jsonl_sink_and_read_back(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        prev = obs.set_event_log(path)
        try:
            obs.event("train_step", step=0, loss=2.5)
            obs.event("train_step", step=1, loss=2.25)
        finally:
            obs.set_event_log(prev)
        evts = obs.read_events(path)
        assert len(evts) == 2 and evts[1]["loss"] == 2.25
        assert obs.read_events(path, n=1)[0]["step"] == 1


# ---------------------------------------------------------------------------
# instrumented layers
# ---------------------------------------------------------------------------


class TestKernelTelemetry:
    def test_gemm_call_counter_labels(self):
        a = jnp.ones((4, 16), jnp.float32)
        b = jnp.ones((16, 8), jnp.float32)
        ops.matmul(a, b, backend="xla")
        snap = obs.snapshot()
        key = (
            "b=array,backend=xla,family=fp,fusion=none,shape=dense,"
            "tile=heuristic"
        )
        assert snap["counters"]["gemm.calls"][key] == 1.0

    def test_grouped_gemm_call_counter(self):
        a = jnp.ones((2, 4, 16), jnp.float32)
        b = jnp.ones((2, 16, 8), jnp.float32)
        ops.grouped_matmul(a, b, backend="xla")
        fam = obs.snapshot()["counters"]["gemm.calls"]
        assert any("shape=grouped" in k for k in fam)

    def test_degradation_counter_and_event(self):
        # compiled pallas cannot lower on CPU: an explicit request degrades
        # along its chain — and the warning now has a telemetry twin
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            resolved = ops.resolve_backend("pallas")
        assert resolved != "pallas"
        fam = obs.snapshot()["counters"]["gemm.degradations"]
        (key,) = fam
        assert "requested=pallas" in key
        assert f"resolved={resolved}" in key
        assert "reason=backend_unavailable" in key
        evt = obs.recent_events(5, kind="degradation")[-1]
        assert evt["requested"] == "pallas" and evt["hop"] >= 1

    def test_tile_lookup_stats_and_counter(self):
        ops.reset_tile_cache_stats()
        ops._tile_for(1234, 256, 128, 4)
        ops._tile_for(1234, 256, 128, 4)
        st = ops.tile_cache_stats()
        assert st["misses"] >= 1 and st["hits"] >= 1
        fam = obs.snapshot()["counters"]["tile.lookups"]
        assert fam["result=miss"] >= 1 and fam["result=hit"] >= 1

    def test_reset_stats_keeps_memo_warm(self):
        ops._tile_for(1235, 256, 128, 4)
        size = ops.tile_cache_info().currsize
        ops.reset_tile_cache_stats()
        assert ops.tile_cache_stats()["misses"] == 0
        assert ops.tile_cache_info().currsize == size
        ops._tile_for(1235, 256, 128, 4)  # still a hit
        assert ops.tile_cache_stats()["hits"] == 1

    def test_miss_streak_hook_fires_at_threshold_multiples(self):
        fired = []
        ops.on_miss_streak(lambda key, s: fired.append(s), threshold=3)
        ops.reset_tile_cache_stats()
        for i in range(7):
            ops._tile_for(4096 + i, 256, 128, 4)
        assert fired == [3, 6]

    def test_hit_resets_the_streak(self):
        fired = []
        ops.on_miss_streak(lambda key, s: fired.append(s), threshold=3)
        ops.reset_tile_cache_stats()
        ops._tile_for(5000, 256, 128, 4)
        ops._tile_for(5001, 256, 128, 4)
        ops._tile_for(5000, 256, 128, 4)  # hit: streak back to 0
        ops._tile_for(5002, 256, 128, 4)
        assert fired == []
        assert ops.tile_cache_stats()["miss_streak"] == 1

    def test_hook_exceptions_are_swallowed(self):
        def bad(key, streak):
            raise RuntimeError("hook bug")

        ops.on_miss_streak(bad, threshold=1)
        ops.reset_tile_cache_stats()
        assert ops._tile_for(6000, 256, 128, 4)  # must not raise

    def test_default_hook_logs_retune_candidate(self):
        ops.on_miss_streak(None, threshold=2)
        ops.reset_tile_cache_stats()
        ops._tile_for(7000, 256, 128, 4, "dense", 0, "xla")
        ops._tile_for(7001, 256, 128, 4, "dense", 0, "xla")
        evts = obs.recent_events(5, kind="retune_candidate")
        assert evts and evts[-1]["m"] == 7001 and evts[-1]["streak"] == 2
        fam = obs.snapshot()["counters"]["tune.retune_candidates"]
        assert fam["backend=xla,family=dense,reason=miss_streak"] == 1.0


class TestReservoirWindow:
    def test_small_histogram_is_exact(self):
        h = obs.histogram("t.win")
        for v in range(10):
            h.observe(float(v))
        assert h.samples_seen == 10 and h.samples_dropped == 0
        s = obs.snapshot()["histograms"]["t.win"][""]
        assert s["samples_seen"] == 10
        assert s["samples_dropped"] == 0
        assert s["percentile_mode"] == "exact"

    def test_overflow_switches_to_windowed(self):
        h = obs.histogram("t.win.big")
        n = 5000  # past the 4096-sample reservoir
        for v in range(n):
            h.observe(float(v))
        assert h.samples_seen == n
        assert h.samples_dropped == n - 4096
        s = obs.snapshot()["histograms"]["t.win.big"][""]
        assert s["percentile_mode"] == "windowed"
        assert s["samples_dropped"] == n - 4096
        # percentiles now describe the newest window, not all time: the
        # oldest samples (0..903) fell out of the deque
        assert s["p50"] >= n - 4096

    def test_count_sum_minmax_stay_alltime(self):
        h = obs.histogram("t.win.stats", buckets=[10.0])
        for v in range(5000):
            h.observe(float(v))
        s = obs.snapshot()["histograms"]["t.win.stats"][""]
        assert s["count"] == 5000
        assert s["min"] == 0.0 and s["max"] == 4999.0
        assert s["buckets"]["+Inf"] == 5000


class TestHistogramProperties:
    """Property tests for the histogram invariants. Uses hypothesis when the
    container has it; the seeded-numpy fuzz versions always run."""

    def _check_monotone(self, values):
        import numpy as np

        obs.reset()
        h = obs.histogram("t.prop", buckets=[0.1, 1.0, 10.0, 100.0])
        for v in values:
            h.observe(float(v))
        s = obs.snapshot()["histograms"]["t.prop"][""]
        counts = list(s["buckets"].values())
        assert counts == sorted(counts), "cumulative buckets must be monotone"
        assert counts[-1] == len(values), "+Inf bucket counts everything"
        if values:
            assert s["min"] == pytest.approx(float(np.min(values)))
            assert s["max"] == pytest.approx(float(np.max(values)))

    def _check_percentile(self, values, q):
        import numpy as np

        got = obs.percentile(list(values), q)
        if not values:
            assert got is None
            return
        assert got == pytest.approx(
            float(np.percentile(np.asarray(values, float), q,
                                method="linear")),
            rel=1e-9, abs=1e-9,
        )

    def test_monotone_buckets_fuzz(self):
        import numpy as np

        rng = np.random.default_rng(7)
        for trial in range(25):
            n = int(rng.integers(0, 50))
            self._check_monotone((rng.lognormal(0, 3, n)).tolist())

    def test_percentile_matches_numpy_fuzz(self):
        import numpy as np

        rng = np.random.default_rng(11)
        for trial in range(50):
            n = int(rng.integers(0, 40))
            xs = rng.standard_normal(n).tolist()
            self._check_percentile(xs, float(rng.uniform(0, 100)))

    @settings(max_examples=50, deadline=None)
    @given(st.lists(st.floats(min_value=1e-6, max_value=1e6), max_size=64))
    def test_monotone_buckets_hypothesis(self, values):
        self._check_monotone(values)

    @settings(max_examples=50, deadline=None)
    @given(
        st.lists(st.floats(min_value=-1e6, max_value=1e6), max_size=64),
        st.floats(min_value=0, max_value=100),
    )
    def test_percentile_matches_numpy_hypothesis(self, values, q):
        self._check_percentile(values, q)


# ---------------------------------------------------------------------------
# utilization attribution (obs.attr) + the util-gap retune seam
# ---------------------------------------------------------------------------


class TestAttr:
    def _rec(self, **kw):
        from repro.obs import attr

        base = dict(
            shape_family="dense", backend="xla", family="fp",
            m=8, k=16, n=8, g=0,
            a_dtype="float32", b_dtype="float32", out_dtype="float32",
            tile_source="heuristic",
            tile_key=("xla", "dense", 8, 16, 8, 0, 4),
        )
        base.update(kw)
        return attr.GemmRecord(**base)

    def test_shape_bucket_pow2_rounds_m_only(self):
        from repro.obs import attr

        assert attr.shape_bucket(self._rec(m=5)) == "dense:8x16x8"
        assert attr.shape_bucket(self._rec(m=8)) == "dense:8x16x8"
        assert attr.shape_bucket(self._rec(m=9)) == "dense:16x16x8"
        grouped = self._rec(shape_family="grouped", g=4, m=3)
        assert attr.shape_bucket(grouped) == "grouped:4x4x16x8"

    def test_capture_is_fed_by_ops(self):
        from repro.obs import attr

        a = jnp.ones((8, 16), jnp.float32)
        b = jnp.ones((16, 8), jnp.float32)
        with attr.capture_gemms() as recs:
            ops.matmul(a, b, backend="xla")
        assert len(recs) == 1
        r = recs[0]
        assert (r.m, r.k, r.n) == (8, 16, 8)
        assert r.backend == "xla" and r.family == "fp"
        assert r.a_dtype == "float32"
        # nothing recorded outside the bracket
        ops.matmul(a, b, backend="xla")
        assert len(recs) == 1

    def test_aggregate_folds_per_class(self):
        from repro.obs import attr

        recs = [self._rec(), self._rec(), self._rec(m=64)]
        wl = attr.aggregate(recs)
        assert len(wl) == 2  # m=8 bucket (x2) and m=64 bucket
        e = wl[("xla", "fp", "dense:8x16x8", "heuristic")]
        assert e.calls == 2
        assert e.flops == pytest.approx(2 * (2.0 * 8 * 16 * 8))
        assert e.roofline_s > 0

    def test_observe_step_populates_histograms(self):
        from repro.obs import attr

        wl = attr.aggregate([self._rec()])
        attr.observe_step(wl, 0.01)
        snap = obs.snapshot()
        key = "backend=xla,bucket=dense:8x16x8,family=fp,tile=heuristic"
        assert snap["histograms"]["gemm.roofline_fraction"][key]["count"] == 1
        assert snap["histograms"]["gemm.achieved_gflops"][key]["count"] == 1
        assert snap["counters"]["gemm.device_seconds"][key] == (
            pytest.approx(0.01)
        )
        frac = snap["histograms"]["gemm.roofline_fraction"][key]["max"]
        assert 0 < frac < 1  # 10ms wall for a tiny GEMM: far off roofline

    def test_observe_step_attributes_proportionally(self):
        from repro.obs import attr

        small, big = self._rec(), self._rec(m=64, k=256, n=256)
        wl = attr.aggregate([small, big])
        attr.observe_step(wl, 1.0)
        fam = obs.snapshot()["counters"]["gemm.device_seconds"]
        assert sum(fam.values()) == pytest.approx(1.0)
        big_key = "backend=xla,bucket=dense:64x256x256,family=fp,tile=heuristic"
        assert fam[big_key] > 0.9  # the big GEMM dominates roofline seconds

    def test_observe_step_guards(self):
        from repro.obs import attr

        attr.observe_step({}, 1.0)  # empty workload: no-op
        attr.observe_step(attr.aggregate([self._rec()]), 0.0)  # no wall time
        assert "gemm.roofline_fraction" not in obs.snapshot()["histograms"]


class TestUtilGap:
    KEY = ("xla", "dense", 64, 256, 256, 0, 4)

    def test_fires_at_streak_multiples(self):
        fired = []
        ops.on_util_gap(
            lambda key, s, f: fired.append((key, s, f)),
            threshold=0.5, streak=2,
        )
        ops._note_util_observation(self.KEY, 0.8, "tuned")  # sets best
        for _ in range(5):
            ops._note_util_observation(self.KEY, 0.1, "tuned")  # 0.1 < 0.4
        assert [(s, f) for _, s, f in fired] == [(2, 0.1), (4, 0.1)]
        assert all(k == self.KEY for k, _, _ in fired)
        fam = obs.snapshot()["counters"]["gemm.util_gap_observations"]
        assert fam[""] == 5.0

    def test_good_observation_resets_the_streak(self):
        fired = []
        ops.on_util_gap(lambda k, s, f: fired.append(s), threshold=0.5,
                        streak=2)
        ops._note_util_observation(self.KEY, 0.8, "tuned")
        ops._note_util_observation(self.KEY, 0.1, "tuned")  # streak 1
        ops._note_util_observation(self.KEY, 0.7, "tuned")  # healthy: reset
        ops._note_util_observation(self.KEY, 0.1, "tuned")  # streak 1 again
        assert fired == []

    def test_heuristic_observations_only_reset(self):
        fired = []
        ops.on_util_gap(lambda k, s, f: fired.append(s), threshold=0.5,
                        streak=2)
        ops._note_util_observation(self.KEY, 0.8, "tuned")
        ops._note_util_observation(self.KEY, 0.1, "tuned")  # streak 1
        ops._note_util_observation(self.KEY, 0.1, "heuristic")  # reset only
        ops._note_util_observation(self.KEY, 0.1, "tuned")  # streak 1
        assert fired == []

    def test_best_only_ratchets_up(self):
        fired = []
        ops.on_util_gap(lambda k, s, f: fired.append(s), threshold=0.5,
                        streak=1)
        ops._note_util_observation(self.KEY, 0.8, "tuned")
        ops._note_util_observation(self.KEY, 0.6, "tuned")  # above 0.4: fine
        assert fired == []
        ops._note_util_observation(self.KEY, 0.3, "tuned")  # below 0.4: gap
        assert fired == [1]

    def test_hook_exceptions_are_swallowed(self):
        def bad(key, streak, fraction):
            raise RuntimeError("hook bug")

        ops.on_util_gap(bad, threshold=0.5, streak=1)
        ops._note_util_observation(self.KEY, 0.8, "tuned")
        ops._note_util_observation(self.KEY, 0.01, "tuned")  # must not raise

    def test_default_hook_logs_retune_candidate(self):
        ops.on_util_gap(None, threshold=0.5, streak=2)
        ops._note_util_observation(self.KEY, 0.8, "tuned")
        ops._note_util_observation(self.KEY, 0.1, "tuned")
        ops._note_util_observation(self.KEY, 0.1, "tuned")
        evts = obs.recent_events(5, kind="retune_candidate")
        assert evts and evts[-1]["reason"] == "util_gap"
        assert evts[-1]["streak"] == 2 and evts[-1]["m"] == 64
        fam = obs.snapshot()["counters"]["tune.retune_candidates"]
        assert fam["backend=xla,family=dense,reason=util_gap"] == 1.0

    def test_parameter_validation(self):
        with pytest.raises(ValueError):
            ops.on_util_gap(None, threshold=0.0)
        with pytest.raises(ValueError):
            ops.on_util_gap(None, threshold=1.5)
        with pytest.raises(ValueError):
            ops.on_util_gap(None, streak=0)

    def test_reset_stats_drops_streaks_and_bests(self):
        fired = []
        ops.on_util_gap(lambda k, s, f: fired.append(s), threshold=0.5,
                        streak=1)
        ops._note_util_observation(self.KEY, 0.8, "tuned")
        ops.reset_tile_cache_stats()
        # best forgotten: 0.1 is now the first (and best) observation
        ops._note_util_observation(self.KEY, 0.1, "tuned")
        assert fired == []


# ---------------------------------------------------------------------------
# shadow numerics auditor (obs.audit)
# ---------------------------------------------------------------------------


class TestAudit:
    def test_q8_policy_is_registered(self):
        from repro.obs import audit

        pol = audit.get_policy("q8")
        assert pol is not None and pol.rel_err == pytest.approx(0.05)

    def test_sampling_off_by_default(self):
        from repro.obs import audit

        assert audit.audit_every() == 0
        a = jnp.ones((8, 16), jnp.float32)
        b = jnp.ones((16, 8), jnp.float32)
        ops.matmul(a, b, backend="xla_q8")
        assert "numerics.audits" not in obs.snapshot()["counters"]

    def test_healthy_q8_audits_clean(self):
        import numpy as np

        from repro.obs import audit

        audit.set_audit_every(1)
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
        ops.matmul(a, b, backend="xla_q8")
        snap = obs.snapshot()
        key = "backend=xla_q8,family=q8,shape=dense"
        assert snap["counters"]["numerics.audits"][key] == 1.0
        rel = snap["histograms"]["numerics.rel_err"][key]
        assert rel["count"] == 1
        assert rel["max"] < 0.05  # well under the q8 policy
        assert "numerics.drift" not in snap["counters"]
        assert obs.recent_events(5, kind="numerics_drift") == []

    def test_fp_family_is_never_audited(self):
        from repro.obs import audit

        audit.set_audit_every(1)
        a = jnp.ones((8, 16), jnp.float32)
        b = jnp.ones((16, 8), jnp.float32)
        ops.matmul(a, b, backend="xla")
        assert "numerics.audits" not in obs.snapshot()["counters"]

    def test_sampling_one_in_n(self):
        import numpy as np

        from repro.obs import audit

        audit.set_audit_every(3)
        rng = np.random.default_rng(1)
        a = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
        for _ in range(6):
            ops.matmul(a, b, backend="xla_q8")
        fam = obs.snapshot()["counters"]["numerics.audits"]
        assert fam["backend=xla_q8,family=q8,shape=dense"] == 2.0

    def test_injected_misscaled_backend_trips_drift(self):
        """The acceptance scenario: a q8 backend whose output is 2x wrong
        must produce a numerics_drift event on the sampled call."""
        import numpy as np

        from repro.obs import audit

        def bad_q8(a, b, c, out_dtype):
            out = (a @ b) * 2.0  # mis-applied dequant scale
            if c is not None:
                out = out + c
            return out.astype(out_dtype)

        ops.register_backend(
            "bad_q8", bad_q8, family="q8", grad_backend="xla",
        )
        try:
            audit.set_audit_every(1)
            rng = np.random.default_rng(2)
            a = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
            b = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
            ops.matmul(a, b, backend="bad_q8")
        finally:
            ops._REGISTRY.pop("bad_q8", None)
        snap = obs.snapshot()
        key = "backend=bad_q8,family=q8,shape=dense"
        assert snap["counters"]["numerics.drift"][key] == 1.0
        evt = obs.recent_events(5, kind="numerics_drift")[-1]
        assert evt["backend"] == "bad_q8" and evt["family"] == "q8"
        assert evt["rel_err"] > 0.5  # a 2x output is ~100% off
        assert evt["threshold"] == pytest.approx(0.05)

    def test_nonfinite_output_is_drift_even_in_threshold(self):
        import numpy as np

        from repro.obs import audit

        def nan_q8(a, b, c, out_dtype):
            out = a @ b
            out = out.at[0, 0].set(jnp.nan)
            if c is not None:
                out = out + c
            return out.astype(out_dtype)

        ops.register_backend(
            "nan_q8", nan_q8, family="q8", grad_backend="xla",
        )
        try:
            audit.set_audit_every(1)
            rng = np.random.default_rng(3)
            a = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
            b = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
            ops.matmul(a, b, backend="nan_q8")
        finally:
            ops._REGISTRY.pop("nan_q8", None)
        snap = obs.snapshot()
        key = "backend=nan_q8,family=q8,sentinel=nan,shape=dense"
        assert snap["counters"]["numerics.nonfinite"][key] == 1.0
        assert obs.recent_events(5, kind="numerics_drift")[-1]["nan"] == 1

    def test_grouped_q8_is_audited(self):
        import numpy as np

        from repro.obs import audit

        audit.set_audit_every(1)
        rng = np.random.default_rng(4)
        a = jnp.asarray(rng.standard_normal((2, 8, 16)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32)
        ops.grouped_matmul(a, b, backend="xla_q8")
        fam = obs.snapshot()["counters"]["numerics.audits"]
        assert fam["backend=xla_q8,family=q8,shape=grouped"] == 1.0

    def test_tracers_are_skipped_inside_jit(self):
        from repro.obs import audit

        audit.set_audit_every(1)
        a = jnp.ones((8, 16), jnp.float32)
        b = jnp.ones((16, 8), jnp.float32)
        jax.jit(lambda a, b: ops.matmul(a, b, backend="xla_q8"))(
            a, b
        ).block_until_ready()
        # the call traced (gemm.calls fired) but the tracer output was not
        # auditable — no shadow execution, no numerics series
        snap = obs.snapshot()
        assert any("xla_q8" in k for k in snap["counters"]["gemm.calls"])
        assert "numerics.audits" not in snap["counters"]

    def test_q8_step_hlo_identical_with_audit_on(self):
        """Sampling on vs off must not change the compiled artifact — the
        auditor is host-side and tracer-skipped."""
        from repro.obs import audit

        a = jnp.ones((8, 16), jnp.float32)
        b = jnp.ones((16, 8), jnp.float32)

        def lower():
            return (
                jax.jit(lambda a, b: ops.matmul(a, b, backend="xla_q8"))
                .lower(a, b).compile().as_text()
            )

        audit.set_audit_every(0)
        off = _instruction_census(lower())
        audit.set_audit_every(1)
        on = _instruction_census(lower())
        assert sum(off.values()) > 0
        assert on == off

    def test_invalid_env_value_means_off(self, monkeypatch):
        from repro.obs import audit

        audit.set_audit_every(None)
        monkeypatch.setenv(audit.AUDIT_ENV, "banana")
        assert audit.audit_every() == 0
        monkeypatch.setenv(audit.AUDIT_ENV, "8")
        assert audit.audit_every() == 8
        monkeypatch.setenv(audit.AUDIT_ENV, "-3")
        assert audit.audit_every() == 0


class TestServingTelemetry:
    @pytest.fixture(scope="class")
    def report_and_snap(self):
        from repro.configs import get_config
        from repro.models import api
        from repro.serve import ContinuousEngine, poisson_trace

        obs.reset()
        cfg = get_config("chatglm3-6b").reduced()
        params = api.init_params(cfg, jax.random.key(0))
        trace = poisson_trace(
            6, seed=0, vocab=cfg.vocab, prompt_lens=(4, 8), gen_lens=(3, 6)
        )
        eng = ContinuousEngine(
            cfg=cfg, params=params, n_slots=2, max_len=32,
            cache_dtype=jnp.float32,
        )
        report = eng.timed_serve(trace)
        return report, obs.snapshot()

    def test_percentiles_are_sane(self, report_and_snap):
        report, _ = report_and_snap
        # a Poisson trace through a 2-slot pool queues: TTFT spans queueing
        # + prefill and must be positive and ordered
        assert 0 < report.ttft_p50 <= report.ttft_p99
        assert 0 < report.itl_p50 <= report.itl_p99
        assert report.ttft_p99 < report.wall_time_s

    def test_lifecycle_histograms_and_counters(self, report_and_snap):
        report, snap = report_and_snap
        h = snap["histograms"]
        assert h["serve.ttft_seconds"][""]["count"] == 6
        # every generated token beyond each request's first closes an
        # inter-token gap
        assert h["serve.itl_seconds"][""]["count"] == (
            report.generated_tokens - 6
        )
        assert h["serve.step_seconds"][""]["count"] == report.decode_steps
        c = snap["counters"]["serve.requests"]
        assert c["event=admitted"] == 6.0 and c["event=retired"] == 6.0
        assert set(snap["gauges"]) >= {"serve.occupancy", "serve.queue_depth"}

    def test_utilization_attribution_populates(self, report_and_snap):
        """The acceptance criterion: live roofline-fraction histograms fill
        during serving — the decode step traced once (capturing its GEMMs)
        and every subsequent execution attributed its wall time."""
        report, snap = report_and_snap
        h = snap["histograms"]
        assert "gemm.roofline_fraction" in h
        assert "gemm.achieved_gflops" in h
        attributed_steps = sum(
            s["count"] for s in h["gemm.roofline_fraction"].values()
        )
        # first decode tick traces (skipped: its wall bracket includes
        # compile); the rest attribute
        assert attributed_steps >= report.decode_steps - 1 > 0
        dev = snap["counters"]["gemm.device_seconds"]
        assert sum(dev.values()) > 0
        # labels carry the full attribution key set
        some = next(iter(dev))
        for part in ("backend=", "bucket=", "family=", "tile="):
            assert part in some

    def test_repro_stats_top_renders(self, report_and_snap, capsys,
                                     tmp_path):
        import json as _json

        from repro.launch.stats import main as stats_main

        _, snap = report_and_snap
        path = tmp_path / "snap.json"
        path.write_text(_json.dumps(snap))
        stats_main(["top", "--file", str(path), "-n", "5"])
        out = capsys.readouterr().out
        assert "bucket" in out and "device_s" in out
        assert "dense:" in out  # decode GEMM buckets ranked

    def test_repro_stats_top_empty_is_friendly(self, capsys, tmp_path):
        import json as _json

        from repro.launch.stats import main as stats_main

        path = tmp_path / "empty.json"
        path.write_text(_json.dumps(
            {"counters": {}, "gauges": {}, "histograms": {}}
        ))
        stats_main(["top", "--file", str(path)])
        assert "no utilization attribution" in capsys.readouterr().out

    def test_bench_row_carries_percentiles(self, report_and_snap):
        import os
        import sys

        report, _ = report_and_snap
        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "benchmarks")
        )
        try:
            from serving_bench import run_continuous
        finally:
            sys.path.pop(0)

        class _Eng:
            def timed_serve(self, requests):
                return report

            def decode_compilations(self):
                return 1

        row = run_continuous(_Eng(), [])
        for k in ("ttft_p50", "ttft_p99", "itl_p50", "itl_p99"):
            assert row[k] == getattr(report, k)


class TestTrainTelemetry:
    def test_per_step_events_with_roofline(self):
        import numpy as np

        from repro.configs import get_config
        from repro.optim.adamw import AdamWConfig
        from repro.train.loop import TrainLoopConfig, train

        cfg = get_config("chatglm3-6b").reduced()

        def batch_fn(step):
            rng = np.random.default_rng(step)
            toks = rng.integers(0, cfg.vocab, (2, 17))
            return {
                "tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                "labels": jnp.asarray(toks[:, 1:], jnp.int32),
            }

        train(
            cfg, AdamWConfig(), TrainLoopConfig(total_steps=3, log_every=0),
            batch_fn, log=lambda m: None,
        )
        evts = obs.recent_events(10, kind="train_step")
        assert [e["step"] for e in evts] == [0, 1, 2]
        for e in evts:
            assert e["tokens"] == 32
            assert e["tokens_per_sec"] > 0
            assert e["gflops_per_sec"] > 0
            assert 0 < e["roofline_frac"] < 1
        snap = obs.snapshot()
        assert snap["histograms"]["train.step_seconds"][""]["count"] == 3
        assert snap["gauges"]["train.tokens_per_sec"][""] > 0


# ---------------------------------------------------------------------------
# the zero-cost claim: telemetry adds NO ops to compiled HLO
# ---------------------------------------------------------------------------

_OPCODE = re.compile(r"=\s*[a-z0-9\[\],{}\s]*?([a-z][a-z0-9\-]*)\(")


def _instruction_census(hlo: str) -> collections.Counter:
    return collections.Counter(
        m.group(1) for line in hlo.splitlines() if " = " in line
        for m in [_OPCODE.search(line)] if m
    )


def test_census_helper_positive_control():
    a = jnp.ones((8, 8))
    t1 = jax.jit(lambda x: x @ x).lower(a).compile().as_text()
    t2 = jax.jit(lambda x: jnp.tanh(x @ x)).lower(a).compile().as_text()
    assert _instruction_census(t1) != _instruction_census(t2)


@pytest.mark.slow
def test_metrics_off_decode_step_hlo_is_identical():
    """REPRO_METRICS=0 must be provably free: the jitted decode step lowers
    to the same instruction census with telemetry on and off, because every
    instrument is host-side Python that runs at trace time only."""
    from repro.configs import ARCHS
    from repro.models import api

    cfg = ARCHS["chatglm3-6b"].reduced()
    params = api.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab)
    _, caches = api.prefill(
        cfg, params, {"tokens": tokens}, max_len=16, cache_dtype=jnp.float32
    )
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.asarray(8, jnp.int32)

    def lower():
        step = jax.jit(lambda p, t, c, q: api.decode(cfg, p, t, c, q))
        return step.lower(params, tok, caches, pos).compile().as_text()

    from repro.obs import tracing

    prev = obs.set_enabled(True)
    tracing.set_enabled(True)  # request tracing must be free too
    try:
        on = _instruction_census(lower())
        obs.set_enabled(False)
        tracing.set_enabled(False)
        off = _instruction_census(lower())
    finally:
        obs.set_enabled(prev)
        tracing.set_enabled(None)

    assert sum(on.values()) > 0
    assert on == off, (
        "telemetry changed the compiled decode step: "
        f"on-off={on - off!r} off-on={off - on!r}"
    )
    # and with metrics ON the trace recorded host-side counters — proof the
    # instrumentation ran during the identical-HLO compile
    assert "gemm.calls" in obs.snapshot()["counters"]
