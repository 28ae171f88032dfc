"""Observability stack (DESIGN.md §15): schema, writer, shadow probes,
monitors, observer, and the report digest.

The load-bearing claim is the probe pin: driving a DenseStore and the
probe shadow with the SAME dedup-summed EMA stream must measure exactly
zero estimation error (the probe replicates the kernels' semantics, so
any gap on a lossless codec would be a probe bug), while an
over-compressed count-min sketch must measure a strictly positive error
(the collision noise the paper's compression argument is about).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cleaning import CleaningSchedule
from repro.core.stores import CountMinStore, CountSketchStore, DenseStore
from repro.obs.metrics import (REQUIRED_FIELDS, SCHEMA_VERSION, MetricsWriter,
                               SchemaError, StepAccumulator, latest,
                               validate_file, validate_record)
from repro.obs.probes import (RunObserver, TableMonitor, TableProbe,
                              predicted_table_errors, probe_row_ids,
                              rows_ema_update)
from repro.obs import profiling
from repro.obs.profiling import LatencyTracker, PhaseTimer, span
from repro.obs.report import analyze
from repro.plan.error_model import TableStats, countmin_error


def _stream(n_rows, dim, steps, batch, seed=0):
    """A deterministic (ids, rows) gradient stream with duplicate ids."""
    k = jax.random.PRNGKey(seed)
    for i in range(steps):
        k, k1, k2 = jax.random.split(k, 3)
        # zipf-ish: half the batch from the head, so probe rows get hit
        head = jax.random.randint(k1, (batch // 2,), 0, 8)
        tail = jax.random.randint(k2, (batch - batch // 2,), 0, n_rows)
        ids = jnp.concatenate([head, tail]).astype(jnp.int32)
        rows = jax.random.normal(jax.random.fold_in(k, i), (batch, dim))
        yield ids, rows


class TestProbeRowIds:
    def test_hot_and_cold_split(self):
        ids = probe_row_ids(10_000, k=16)
        assert len(ids) == 16 and len(set(ids)) == 16
        assert list(ids[:8]) == list(range(8))          # zipf head
        assert all(i >= 8 for i in ids[8:])             # spread in tail
        assert max(ids) < 10_000

    def test_tiny_table_clamps(self):
        ids = probe_row_ids(4, k=16)
        assert len(ids) == len(set(ids)) <= 4


class TestProbePin:
    """The acceptance pin: probe error == 0 dense, > 0 over-compressed."""

    N, D, BATCH, STEPS = 1000, 4, 32, 25

    def _drive(self, store):
        """Run the same stream through ``store`` (via the kernels' dedup
        EMA semantics) and the probe shadow; return the measured errors."""
        probe = TableProbe.for_table("t", self.N, k=8,
                                     track_first_moment=False)
        pstate = probe.init(self.D)
        state = store.init()
        for ids, rows in _stream(self.N, self.D, self.STEPS, self.BATCH):
            state = rows_ema_update(store, state, ids, rows, probe.b2,
                                    square=True)
            pstate = probe.update(pstate, ids, rows)
        return probe.errors(pstate, v_store=store, v_state=state)

    def test_dense_store_measures_zero(self):
        store = DenseStore().bind("t", (self.N, self.D), jnp.float32)
        errs = self._drive(store)
        assert errs["probe_rows_seen"] >= 4
        np.testing.assert_allclose(errs["v_meas_error"], 0.0, atol=1e-5)

    def test_overcompressed_sketch_measures_error(self):
        # width 8 for 1000 rows: ~125 rows per bucket — collisions certain
        store = CountMinStore(depth=1, width=8).bind(
            "t", (self.N, self.D), jnp.float32)
        errs = self._drive(store)
        assert errs["v_meas_error"] > 0.1
        # tail rows collide with the (heavy) head → cold error dominates
        assert errs["v_meas_error_cold"] > 0.0

    def test_probe_state_rides_in_jit(self):
        """update() under jit with donation — the launcher integration."""
        probe = TableProbe.for_table("t", self.N, k=8)
        pstate = probe.init(self.D)
        upd = jax.jit(probe.update, donate_argnums=(0,))
        for ids, rows in _stream(self.N, self.D, 3, self.BATCH):
            pstate = upd(pstate, ids, rows)
        assert int(jnp.sum(pstate["hits"])) > 0


class TestSchema:
    def test_validate_good_records(self):
        validate_record({"schema": SCHEMA_VERSION, "kind": "step",
                         "step": 10, "steps_per_s": 42.0, "loss": 1.25})
        validate_record({"schema": SCHEMA_VERSION, "kind": "table",
                         "step": 10, "table": "emb", "v_occupancy": 0.4})

    @pytest.mark.parametrize("rec, msg", [
        ({"kind": "step", "step": 1, "steps_per_s": 1.0}, "schema version"),
        ({"schema": SCHEMA_VERSION, "kind": "nope"}, "unknown record kind"),
        ({"schema": SCHEMA_VERSION, "kind": "step", "step": 1},
         "missing required field"),
        ({"schema": SCHEMA_VERSION, "kind": "step", "step": -1,
          "steps_per_s": 1.0}, "non-negative"),
        ({"schema": SCHEMA_VERSION, "kind": "step", "step": 1,
          "steps_per_s": float("nan")}, "non-finite"),
        ({"schema": SCHEMA_VERSION, "kind": "step", "step": 1,
          "steps_per_s": float("inf")}, "non-finite"),
    ])
    def test_validate_rejects(self, rec, msg):
        with pytest.raises(SchemaError, match=msg):
            validate_record(rec)

    def test_every_kind_has_required_fields(self):
        for kind, fields in REQUIRED_FIELDS.items():
            assert isinstance(fields, tuple) and fields


class TestMetricsWriter:
    def test_round_trip(self, tmp_path):
        with MetricsWriter(tmp_path, run_meta={"workload": "x"},
                           flush_every=2) as w:
            w.write("step", step=10, steps_per_s=12.5, loss=0.5)
            w.write("table", step=10, table="emb", v_occupancy=0.25)
        recs = validate_file(tmp_path / "metrics.jsonl")
        assert [r["kind"] for r in recs] == ["meta", "step", "table"]
        assert recs[0]["run"] == {"workload": "x"}
        assert latest(recs, "table", table="emb")["v_occupancy"] == 0.25

    def test_write_rejects_bad_record_before_buffering(self, tmp_path):
        w = MetricsWriter(tmp_path)
        with pytest.raises(SchemaError):
            w.write("step", step=1, steps_per_s=float("nan"))
        w.close()
        assert len(validate_file(w.path)) == 1      # just the meta record

    def test_validate_file_flags_corrupt_line(self, tmp_path):
        p = tmp_path / "metrics.jsonl"
        p.write_text(json.dumps({"schema": SCHEMA_VERSION, "kind": "meta",
                                 "run": {}}) + "\nnot json\n")
        with pytest.raises(SchemaError, match=":2"):
            validate_file(p)


class TestStepAccumulator:
    def test_on_device_means(self):
        acc = StepAccumulator()
        for v in (1.0, 2.0, 3.0):
            acc.add({"loss": jnp.asarray(v)})
        assert acc.count == 3
        out = acc.drain()
        np.testing.assert_allclose(out["loss"], 2.0)
        assert acc.count == 0 and acc.drain() == {}


class TestObserverEndToEnd:
    """Monitor + observer over a real sketched table, then the report."""

    N, D = 512, 4

    def _run(self, tmp_path, steps=20, log_every=10):
        m_store = CountSketchStore(depth=1, width=8).bind(
            "t", (self.N, self.D), jnp.float32)
        v_store = CountMinStore(depth=1, width=8,
                                cleaning=CleaningSchedule(0.5, 7)).bind(
            "t", (self.N, self.D), jnp.float32)
        probe = TableProbe.for_table("t", self.N, k=8)
        mon = TableMonitor(
            path="t", m_store=m_store, v_store=v_store, probe=probe,
            predicted=predicted_table_errors(m_store, v_store, self.N))
        obs = RunObserver(MetricsWriter(tmp_path, run_meta={"n": self.N}),
                          monitors=[mon], log_every=log_every,
                          phase_timer=PhaseTimer())
        st = {"m": m_store.init(), "v": v_store.init(),
              "probe": probe.init(self.D)}
        for i, (ids, rows) in enumerate(
                _stream(self.N, self.D, steps, 32), start=1):
            with span("step", obs.phase_timer):
                st["m"] = rows_ema_update(m_store, st["m"], ids, rows,
                                          probe.b1)
                st["v"] = rows_ema_update(v_store, st["v"], ids, rows,
                                          probe.b2, square=True)
                st["probe"] = probe.update(st["probe"], ids, rows)
            obs.on_step(i, {"step": i, "time_s": 1e-3, "loss": 1.0}, st)
        obs.close(steps, st)
        return validate_file(tmp_path / "metrics.jsonl")

    def test_emits_all_kinds_at_boundaries(self, tmp_path):
        recs = self._run(tmp_path)
        kinds = [r["kind"] for r in recs]
        assert kinds.count("step") == 2 and kinds.count("phase") == 2
        # double-buffered collect: boundary N's stats surface one
        # boundary later, the last one at close() — both step labels land
        tables = [r for r in recs if r["kind"] == "table"]
        assert [t["step"] for t in tables] == [10, 20]
        last = tables[-1]
        for field in ("v_occupancy", "v_mass", "v_meas_error",
                      "v_pred_error", "v_error_ratio", "m_sign_cancel",
                      "probe_rows_seen", "cleans_in_window",
                      "v_clean_next_removes"):
            assert field in last, field
        # cadence-7 cleaning fires once in the (10, 20] window (step 14)
        assert last["cleans_in_window"] == 1
        assert last["v_meas_error"] > 0.0           # over-compressed

    def test_report_analyze_warns_on_overcompressed(self, tmp_path):
        digest = analyze(self._run(tmp_path))
        cats = {w.split(":")[0] for w in digest["warnings"]}
        assert "probe-error" in cats
        assert digest["meta"]["run"] == {"n": self.N}
        assert "t" in digest["tables"]

    def test_report_healthy_on_dense(self, tmp_path):
        w = MetricsWriter(tmp_path, run_meta={})
        w.write("step", step=10, steps_per_s=10.0)
        # a dense table: occupancy may be high but pred_error == 0.0
        # marks it lossless — no saturation warning applies
        w.write("table", step=10, table="t", v_occupancy=0.99,
                v_pred_error=0.0, v_meas_error=0.0)
        w.close()
        digest = analyze(validate_file(w.path))
        assert digest["warnings"] == []


class TestPredictedErrors:
    def test_matches_error_model_at_store_geometry(self):
        v = CountMinStore(depth=2, width=64).bind("t", (1000, 4),
                                                  jnp.float32)
        pred = predicted_table_errors(None, v, 1000, alpha=1.1)
        want = countmin_error(TableStats(alpha=1.1), 1000, 64, 2)
        np.testing.assert_allclose(pred["v_pred_error"], want)
        assert "m_pred_error" not in pred

    def test_dense_predicts_zero(self):
        d = DenseStore().bind("t", (100, 4), jnp.float32)
        assert predicted_table_errors(d, d, 100) == {
            "m_pred_error": 0.0, "v_pred_error": 0.0}


class TestStoreStats:
    def test_gauges_and_sampling_consistency(self):
        st = CountMinStore(depth=2, width=32).bind("t", (256, 8),
                                                   jnp.float32)
        state = jnp.abs(jax.random.normal(jax.random.PRNGKey(0),
                                          st.init().shape))
        out = {k: float(v) for k, v in st.stats(state).items()}
        # small sketch → stride 1 → gauges are exact
        np.testing.assert_allclose(out["mass"],
                                   float(jnp.sum(jnp.abs(state))), rtol=1e-6)
        np.testing.assert_allclose(out["occupancy"], 1.0)
        assert out["sign_cancel"] < 1e-6            # all-positive cells

    def test_sampled_mass_scales_up(self):
        st = CountSketchStore(depth=1, width=8).bind("t", (64, 4),
                                                     jnp.float32)
        big = jnp.ones((4 * st.STATS_SAMPLE_CELLS,), jnp.float32)
        out = st.stats(big)
        np.testing.assert_allclose(float(out["mass"]), big.size, rtol=0.01)
        np.testing.assert_allclose(float(out["occupancy"]), 1.0)


class TestLatencyTracker:
    def test_percentiles(self):
        lt = LatencyTracker(capacity=128)
        for ms in range(1, 101):
            lt.record(ms / 1e3)
        s = lt.summary()
        assert s["count"] == 100
        assert 45 <= s["p50_ms"] <= 55 and 95 <= s["p99_ms"] <= 100


class TestServeTelemetry:
    def test_timed_adapt_emits_schema_valid_serve_record(self, tmp_path):
        from repro.serve.steps import timed_adapt

        adapt, lat = timed_adapt(
            lambda table, st, ids, rows: (table + 1.0, st))
        table, st = jnp.zeros((4, 2)), {}
        for _ in range(5):
            table, st = adapt(table, st, jnp.zeros((2,), jnp.int32),
                              jnp.zeros((2, 2)))
        assert lat.count == 5 and float(table[0, 0]) == 5.0
        with MetricsWriter(tmp_path, run_meta={}) as w:
            w.write("serve", adapt_ms=lat.summary(),
                    reads_per_s=lat.per_second())
        recs = validate_file(tmp_path / "metrics.jsonl")
        assert recs[-1]["adapt_ms"]["count"] == 5


class TestServeSloWarnings:
    """report.analyze serve-section SLO gates (DESIGN.md §16): p99 above
    the record's own slo_p99_ms (or the --serve-p99-warn fallback) and
    nonzero shed rate both warn — and --strict turns them into exit 1."""

    def _hist(self, p99):
        return {"count": 10, "mean_ms": p99 / 2, "p50_ms": p99 / 2,
                "p90_ms": p99 * 0.9, "p99_ms": p99, "max_ms": p99}

    def _serve(self, **kw):
        return {"schema": SCHEMA_VERSION, "kind": "serve", **kw}

    def test_p99_over_record_slo_warns(self):
        digest = analyze([self._serve(adapt_ms=self._hist(80.0),
                                      slo_p99_ms=50.0, shed_rate=0.0)])
        cats = {w.split(":")[0] for w in digest["warnings"]}
        assert cats == {"serve-slo"}

    def test_fallback_threshold_when_record_has_no_slo(self):
        rec = self._serve(adapt_ms=self._hist(80.0))
        assert analyze([rec])["warnings"] == []
        digest = analyze([rec], serve_p99_warn=50.0)
        assert any(w.startswith("serve-slo") for w in digest["warnings"])

    def test_nonzero_shed_warns(self):
        digest = analyze([self._serve(adapt_ms=self._hist(1.0),
                                      slo_p99_ms=50.0, shed_rate=0.25,
                                      n_shed=5, n_requests=20)])
        warns = [w for w in digest["warnings"]]
        assert len(warns) == 1 and warns[0].startswith("serve-shed")
        assert "5/20" in warns[0]

    def test_healthy_serve_no_warnings(self):
        digest = analyze([self._serve(adapt_ms=self._hist(10.0),
                                      slo_p99_ms=50.0, shed_rate=0.0)])
        assert digest["warnings"] == []

    def test_strict_exit_and_render(self, tmp_path):
        import io

        from repro.obs.report import main, render
        with MetricsWriter(tmp_path, run_meta={}) as w:
            w.write("serve", adapt_ms=self._hist(80.0), slo_p99_ms=50.0,
                    shed_rate=0.1, n_shed=2, n_requests=20, n_batches=4,
                    request_ms=self._hist(90.0), reads_per_s=100.0)
        path = str(tmp_path / "metrics.jsonl")
        assert main([path]) == 0                      # non-strict: report only
        assert main([path, "--strict"]) == 1
        buf = io.StringIO()
        render(analyze(validate_file(path)), out=buf)
        out = buf.getvalue()
        assert "serve-slo" in out and "serve-shed" in out
        assert "p50" in out and "p99" in out
        assert "request latency" in out and "shed: 2/20" in out


class TestQuantNoiseGauge:
    """The quantization-error gauge (DESIGN.md §18): int8 stores emit a
    ``*_quant_noise`` envelope in the probe's rel-L1 units, and it feeds
    the ``*_error_ratio`` denominator so the calibration signal stays
    O(1) at every cell dtype."""

    N, D, BATCH, STEPS = 1000, 4, 32, 25

    def _drive(self, store):
        probe = TableProbe.for_table("t", self.N, k=8,
                                     track_first_moment=False)
        pstate = probe.init(self.D)
        state = store.init()
        for ids, rows in _stream(self.N, self.D, self.STEPS, self.BATCH):
            state = rows_ema_update(store, state, ids, rows, probe.b2,
                                    square=True)
            pstate = probe.update(pstate, ids, rows)
        return probe.errors(pstate, v_store=store, v_state=state)

    def test_int8_emits_positive_gauge(self):
        store = CountMinStore(compression=4.0, dtype="int8").bind(
            "t", (self.N, self.D), jnp.float32)
        errs = self._drive(store)
        assert errs["v_quant_noise"] > 0.0
        # the gauge is an envelope in the SAME units as meas_error:
        # quantization alone cannot explain MORE error than measured
        # by orders of magnitude
        assert errs["v_quant_noise"] < 100 * max(errs["v_meas_error"],
                                                 1e-6)

    def test_f32_has_no_gauge(self):
        store = CountMinStore(compression=4.0).bind(
            "t", (self.N, self.D), jnp.float32)
        errs = self._drive(store)
        assert "v_quant_noise" not in errs


class TestProfiling:
    """Host spans, the scope map of compiled instructions, the phase timer
    and the compile counter (``obs/profiling.py``)."""

    def test_scope_map_innermost_wins(self):
        text = "\n".join([
            "HloModule m, entry_computation_layout={(f32[4])->f32[4]}",
            "ENTRY %main (p: f32[4]) -> f32[4] {",
            "  %p = f32[4]{0} parameter(0)",
            '  %a.1 = f32[4]{0} sine(%p), metadata={op_name="jit(f)/'
            'obs.kernel/sin" source_file="f.py" source_line=3}',
            '  %b = f32[4]{0} cosine(%a.1), metadata={op_name="jit(f)/cos"}',
            '  ROOT %sort.2 = f32[4]{0} sort(%b), metadata={op_name="jit(f)/'
            'obs.kernel/obs.dedup/jit(argsort)/sort"}',
            "}"])
        assert profiling.scope_map(text) == {
            "p": "unscoped", "a.1": "obs.kernel", "b": "unscoped",
            "sort.2": "obs.dedup"}

    def test_scope_map_reads_metadata_after_a_line_break(self):
        """A Pallas kernel's frontend attributes hold line breaks: its
        metadata comes lines after the instruction's name."""
        text = "\n".join([
            "ENTRY %main (p: f32[4]) -> f32[4] {",
            "  %p = f32[4]{0} parameter(0)",
            '  %splash_mha_fwd.3 = f32[4]{0} custom-call(%p), custom_call_'
            'target="tpu_custom_call", frontend_attributes={kernel_'
            'metadata={',
            '"xprof_metadata":"{\\"block_q\\": 512}"',
            '}}, metadata={op_name="jit(f)/obs.attn/vmap(jit(_splash))/'
            'pallas_call"}',
            '  ROOT %c = f32[4]{0} cosine(%splash_mha_fwd.3)',
            "}"])
        assert profiling.scope_map(text) == {
            "p": "unscoped", "splash_mha_fwd.3": "obs.attn",
            "c": "unscoped"}

    def test_scope_map_of_compiled_function(self):
        def f(x):
            with profiling.scope("obs.kernel"):
                y = jnp.sin(x) * 3.0
                with profiling.scope("obs.dedup"):
                    z = jnp.sort(y)
            return z + jnp.cos(x).sum()

        text = jax.jit(f).lower(jnp.ones((64,))).compile().as_text()
        m = profiling.scope_map(text)
        sorts = [ln.split(" = ")[0].split("%")[-1]
                 for ln in text.splitlines() if " = " in ln and " sort(" in ln]
        assert sorts and all(m[k] == "obs.dedup" for k in sorts)
        assert {"obs.dedup", "unscoped"} <= set(m.values())
        assert set(m.values()) <= {"obs.kernel", "obs.dedup", "unscoped"}

    def test_phase_timer_drain_max_ms(self):
        t = PhaseTimer()
        for s in (0.001, 0.003, 0.002):
            t.add("train.wait", s)
        t.add("train.data", 0.0005)
        out = t.drain()
        assert out["train.wait"] == {"count": 3, "total_ms": 6.0,
                                     "mean_ms": 2.0, "max_ms": 3.0}
        assert out["train.data"]["max_ms"] == 0.5
        assert t.drain() == {}

    def test_span_records_on_error(self):
        t = PhaseTimer()
        with pytest.raises(ValueError):
            with span("train.data", t):
                raise ValueError("boom")
        assert t.drain()["train.data"]["count"] == 1

    def test_compile_counter(self):
        f = jax.jit(lambda x: x * 1.2345 + 0.5)
        x = jnp.ones((7, 3))
        with profiling.CompileCounter() as c:
            f(x).block_until_ready()
            f(x).block_until_ready()          # cached: no new compile
        assert c.count == 1 and c.seconds > 0
        jax.jit(lambda x: x - 0.25)(x).block_until_ready()
        assert c.count == 1                   # closed: listens no more

    def test_trainer_opens_train_spans_in_order(self, tmp_path):
        from jax.profiler import ProfileData

        from repro.train.trainer import Trainer, TrainerConfig, TrainState

        class Data:
            def batch(self, step):
                return {"x": np.full((4,), step, np.float32)}

        class Cleaner:
            def maybe_dispatch(self, opt_state, step):
                return opt_state, False

        def step_fn(p, s, batch):
            p = p + batch["x"].mean()
            return p, s + 1, {"loss": jnp.sum(p)}

        tr = Trainer(jax.jit(step_fn), Data(), TrainerConfig(total_steps=2),
                     cleaner=Cleaner())
        state = TrainState(step=0, params=jnp.zeros(()),
                           opt_state=jnp.zeros((), jnp.int32))
        tr.fit(state)                         # compile outside the trace
        tr.phases.drain()
        tr.tcfg.total_steps = 4
        jax.profiler.start_trace(str(tmp_path))
        try:
            tr.fit(TrainState(2, state.params, state.opt_state))
        finally:
            jax.profiler.stop_trace()
        path = next(tmp_path.rglob("*.xplane.pb"))
        events = sorted(
            (ev.start_ns, ev.name)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("train."))
        step = ["train.data", "train.feed", "train.clean", "train.dispatch",
                "train.wait", "train.record", "train.checkpoint"]
        assert [n for _, n in events] == step + step + ["train.checkpoint"]
        phases = tr.phases.drain()
        assert sorted(phases) == sorted(step)
        assert phases["train.checkpoint"]["count"] == 3
        assert all(phases[n]["count"] == 2 for n in step[:-1])
