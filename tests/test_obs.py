"""Tests for repro.obs: span tracing, the metrics registry, the bench
artifact schema/writer and log_step."""
import io
import json
import math
import os
import time

import pytest

from repro.obs import (
    Gate,
    MetricsRegistry,
    SchemaError,
    Tracer,
    bench_name_from_path,
    get_registry,
    get_tracer,
    log_step,
    set_tracer,
    use_registry,
    use_tracer,
    traced,
    validate_bench,
    write_bench,
)
from repro.obs.schema import _check_gate, _sweep_finite
from repro.obs.trace import NOOP
from repro.pipeline import PipelinedRunner


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------- tracer

class TestTracer:
    def test_span_records_name_track_args(self):
        clk = FakeClock()
        tr = Tracer(capacity=8, clock=clk)
        clk.t = 1.0
        with tr.span("decide", track="decide", step=7):
            clk.t = 1.5
        (ev,) = tr.events()
        assert ev["name"] == "decide" and ev["track"] == "decide"
        assert ev["args"] == {"step": 7}
        assert ev["ts"] == 1.0 and ev["dur"] == 0.5

    def test_ring_drops_oldest(self):
        tr = Tracer(capacity=3, clock=FakeClock())
        for i in range(5):
            tr.span(f"s{i}").end()
        assert [e["name"] for e in tr.events()] == ["s2", "s3", "s4"]
        assert tr.dropped == 2

    def test_end_is_idempotent(self):
        tr = Tracer(capacity=4, clock=FakeClock())
        with tr.span("a") as h:
            h.end()
        assert len(tr.events()) == 1

    def test_start_span_crosses_scopes(self):
        clk = FakeClock()
        tr = Tracer(capacity=4, clock=clk)
        h = tr.start_span("train", track="train/0", step=0)
        clk.t = 2.0
        tr.span("decide", track="decide", step=1).end()
        clk.t = 3.0
        h.end()
        names = [e["name"] for e in tr.events()]   # completion order
        assert names == ["decide", "train"]
        train = tr.events()[1]
        assert train["ts"] == 0.0 and train["dur"] == 3.0

    def test_durations_aggregate(self):
        clk = FakeClock()
        tr = Tracer(capacity=8, clock=clk)
        for dur in (1.0, 3.0):
            h = tr.span("x")
            clk.t += dur
            h.end()
        h = tr.span("y")
        clk.t += 10.0
        h.end()
        rows = tr.durations()
        assert rows[0]["name"] == "y" and rows[0]["total_s"] == 10.0
        assert rows[1] == {"name": "x", "count": 2, "total_s": 4.0,
                           "mean_s": 2.0, "max_s": 3.0}

    def test_chrome_export_matches_handwritten_oracle(self, tmp_path):
        """Nested spans on one track against the trace_event document we
        expect Perfetto to parse: meta row first, X events sorted by ts,
        microsecond units relative to the trace epoch."""
        clk = FakeClock()
        tr = Tracer(capacity=8, clock=clk)        # epoch 0.0
        clk.t = 1.0
        outer = tr.start_span("outer", track="main", step=0)
        clk.t = 2.0
        inner = tr.span("inner", track="main")
        clk.t = 3.0
        inner.end()
        clk.t = 4.0
        outer.end()
        path = tmp_path / "trace.json"
        tr.export(path)
        doc = json.loads(path.read_text())
        pid = os.getpid()
        thread = tr.events()[0]["thread"]
        assert doc == {
            "traceEvents": [
                {"name": "thread_name", "ph": "M", "pid": pid, "tid": 0,
                 "args": {"name": "main"}},
                {"name": "outer", "ph": "X", "cat": "repro", "pid": pid,
                 "tid": 0, "ts": 1000000.0, "dur": 3000000.0,
                 "args": {"step": 0, "thread": thread}},
                {"name": "inner", "ph": "X", "cat": "repro", "pid": pid,
                 "tid": 0, "ts": 2000000.0, "dur": 1000000.0,
                 "args": {"thread": thread}},
            ],
            "displayTimeUnit": "ms",
        }

    def test_tracks_become_distinct_tids(self):
        tr = Tracer(capacity=8, clock=FakeClock())
        tr.span("a", track="t0").end()
        tr.span("b", track="t1").end()
        doc = tr.chrome_trace()
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert {m["args"]["name"] for m in meta} == {"t0", "t1"}
        assert len({m["tid"] for m in meta}) == 2

    def test_noop_is_default_and_allocation_free(self):
        assert get_tracer() is NOOP
        # one shared handle, no per-call state
        assert NOOP.span("a", track="x", step=1) is NOOP.span("b")
        assert NOOP.events() == [] and NOOP.durations() == []

    def test_set_tracer_restores(self):
        tr = Tracer(capacity=4)
        prev = set_tracer(tr)
        try:
            assert get_tracer() is tr
        finally:
            set_tracer(prev)
        assert get_tracer() is NOOP
        with use_tracer(Tracer(capacity=4)) as t2:
            assert get_tracer() is t2
        assert get_tracer() is NOOP

    def test_traced_decorator_resolves_at_call_time(self):
        @traced("work", track="lib")
        def work(x):
            return x + 1

        assert work(1) == 2                      # disabled: plain call
        with use_tracer(Tracer(capacity=4, clock=FakeClock())) as tr:
            assert work(2) == 3
        (ev,) = tr.events()
        assert ev["name"] == "work" and ev["track"] == "lib"

    def test_overhead_smoke(self):
        """Loose smoke: 20k noop span sites and 20k live spans both
        complete far under any per-step budget."""
        t0 = time.perf_counter()
        for _ in range(20_000):
            with get_tracer().span("hot", track="x"):
                pass
        noop_s = time.perf_counter() - t0
        assert noop_s < 1.0, noop_s
        tr = Tracer(capacity=1024)
        t0 = time.perf_counter()
        with use_tracer(tr):
            for _ in range(20_000):
                with get_tracer().span("hot", track="x"):
                    pass
        live_s = time.perf_counter() - t0
        assert live_s < 3.0, live_s
        assert tr.dropped == 20_000 - 1024


class TestRunnerBitwise:
    """The disabled tracer must be invisible to the pipelined runner."""

    @staticmethod
    def _records(depth, tracer=None):
        def decide(state, batch):
            return batch % 3, 0.5 * batch

        def advance(state, batch, assign):
            return (batch, assign), state + 1, {"aux": batch}

        def train(train_input):
            b, a = train_input
            return math.sin(b * 1.7 + a)

        r = PipelinedRunner(decide, advance, train, 0, depth=depth)
        prev = set_tracer(tracer)
        try:
            return r.run(range(10))
        finally:
            set_tracer(prev)

    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_noop_vs_traced_bitwise(self, depth):
        base = self._records(depth)                       # NOOP (default)
        traced_run = self._records(depth, tracer=Tracer(capacity=256))
        assert base == traced_run                          # float-exact

    def test_traced_runner_emits_expected_spans(self):
        tr = Tracer(capacity=256)
        self._records(2, tracer=tr)
        names = {e["name"] for e in tr.events()}
        assert {"batch.next", "decide", "advance", "train", "train.issue",
                "loss.wait", "record"} <= names
        tracks = {e["track"] for e in tr.events() if e["name"] == "train"}
        assert tracks == {"train/0", "train/1"}


class TestRunnerPhases:
    """The runner's main-thread spans tile its loop: they never overlap,
    and every batch pull, stage call and record falls inside the span of
    its phase, so a host gap between two device programs lies in exactly
    one of them."""

    PHASES = ("batch.next", "decide", "advance", "train.issue",
              "loss.wait", "record")

    @staticmethod
    def _run(depth, ahead, tracer, steps=6):
        calls = []                      # (phase, host time inside it)

        def mark(phase):
            calls.append((phase, time.perf_counter()))

        def batches():
            for b in range(steps):
                mark("batch.next")
                yield b

        def decide(state, batch):
            mark("decide")
            return batch % 3, None

        def advance(state, batch, assign):
            mark("advance")
            return (batch, assign), state + 1, {}

        def train(x):
            mark("train.issue")
            return math.sin(x[0] * 1.7 + x[1])

        def record(t, loss, aux, info):
            mark("record")
            return {"step": t, "loss": loss}

        def repair(committed, decided, batch, assign):
            mark("repair")
            return assign, {}

        r = PipelinedRunner(decide, advance, train, 0, depth=depth,
                            decide_ahead=ahead,
                            repair_fn=repair if ahead else None)
        with use_tracer(tracer):
            recs = r.run(batches(), record_fn=record)
        return recs, calls

    @pytest.mark.parametrize("depth,ahead", [(1, 0), (2, 0), (2, 1)],
                             ids=["depth1", "depth2", "decide_ahead1"])
    def test_main_thread_spans_tile_the_loop(self, depth, ahead):
        tr = Tracer(capacity=1024)
        recs, calls = self._run(depth, ahead, tr)
        assert [r["step"] for r in recs] == list(range(6))
        flat = sorted((e for e in tr.events() if e["name"] != "train"),
                      key=lambda e: e["ts"])
        assert {e["thread"] for e in flat} == {"MainThread"}
        for a, b in zip(flat, flat[1:]):            # no nesting, no overlap
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-9, (a, b)
        for phase, at in calls:
            at -= tr.t0
            assert any(e["name"] == phase
                       and e["ts"] - 1e-9 <= at <= e["ts"] + e["dur"] + 1e-9
                       for e in flat), phase
        counts = {n: sum(e["name"] == n for e in flat) for n in self.PHASES}
        # six batches and the pull that finds the stream ended
        assert counts == dict.fromkeys(self.PHASES, 6) | {"batch.next": 7}
        assert sum(e["name"] == "repair" for e in flat) == (6 if ahead else 0)
        windows = [e for e in tr.events() if e["name"] == "train"]
        assert len(windows) == 6

    @staticmethod
    def _host_names(tmp_path, tracer):
        import jax

        jax.profiler.start_trace(str(tmp_path))
        try:
            TestRunnerPhases._run(2, 0, tracer)
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(str(path))
        host = [p for p in data.planes if p.name.startswith("/host:")]
        return [(ev.name, {k: v for k, v in ev.stats}) for p in host
                for ln in p.lines for ev in ln.events]

    def test_profiler_capture_holds_the_phases(self, tmp_path):
        events = self._host_names(tmp_path, Tracer(capacity=1024))
        names = {n for n, _ in events}
        assert set(self.PHASES) <= names
        steps = sorted(st["step"] for n, st in events if n == "record")
        assert steps == list(range(6))
        # the in-flight windows end out of order: not annotations
        assert "train" not in names

    def test_no_tracer_no_annotation(self, tmp_path):
        names = {n for n, _ in self._host_names(tmp_path, None)}
        assert not set(self.PHASES) & names


# ------------------------------------------------------------- registry

class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        reg.counter("exchange.wire_bytes").inc(10)
        reg.counter("exchange.wire_bytes").inc(5)
        reg.gauge("elastic.n_active").set(8)
        h = reg.histogram("sim.iter_time_s", keep=True)
        h.observe(1.0)
        h.observe(3.0)
        assert reg.value("exchange.wire_bytes") == 15
        assert reg.value("elastic.n_active") == 8
        assert h.samples == [1.0, 3.0] and h.mean == 2.0
        snap = reg.snapshot()
        assert snap["sim.iter_time_s"] == {
            "kind": "histogram", "count": 2, "sum": 4.0,
            "min": 1.0, "max": 3.0, "mean": 2.0}
        assert list(snap) == sorted(snap)

    def test_kind_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")
        with pytest.raises(TypeError):
            reg.histogram("x")

    def test_record_step_is_legacy_shaped_and_folds_namespace(self):
        reg = MetricsRegistry()
        r0 = reg.record_step(0, {"loss": 0.5, "miss_pull": 10,
                                 "cost": 0.25, "n_active": 7})
        r1 = reg.record_step(1, {"loss": 0.4, "miss_pull": 3,
                                 "cost": 0.5, "skipped_unknown": 1})
        # the legacy view: same dicts, in order, step folded in front
        assert reg.steps == [r0, r1]
        assert r0 == {"step": 0, "loss": 0.5, "miss_pull": 10,
                      "cost": 0.25, "n_active": 7}
        # counters accumulate, gauges keep the last value
        assert reg.value("cache.miss_pull") == 13
        assert reg.value("dispatch.cost_s") == 0.75
        assert reg.value("train.loss") == 0.4
        assert reg.value("elastic.n_active") == 7
        assert "skipped_unknown" not in reg.snapshot()

    def test_use_registry_restores(self):
        outer = get_registry()
        with use_registry() as reg:
            assert get_registry() is reg and reg is not outer
        assert get_registry() is outer


class TestHistogramQuantile:
    def _hist(self, values, keep=True):
        h = MetricsRegistry().histogram("h", keep=keep)
        for v in values:
            h.observe(v)
        return h

    def test_linear_interpolation_matches_numpy(self):
        import numpy as np
        vals = [5.0, 1.0, 3.0, 2.0, 4.0, 9.0, 0.5]
        h = self._hist(vals)
        for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
            assert h.quantile(q) == pytest.approx(
                float(np.quantile(vals, q)))

    def test_empty_returns_nan(self):
        assert math.isnan(self._hist([]).quantile(0.5))

    def test_single_sample_is_every_quantile(self):
        h = self._hist([7.25])
        for q in (0.0, 0.5, 0.99, 1.0):
            assert h.quantile(q) == 7.25

    def test_keep_false_raises_typeerror(self):
        h = self._hist([1.0, 2.0], keep=False)
        with pytest.raises(TypeError, match="keep"):
            h.quantile(0.5)

    def test_out_of_range_q_raises(self):
        h = self._hist([1.0])
        with pytest.raises(ValueError):
            h.quantile(-0.01)
        with pytest.raises(ValueError):
            h.quantile(1.01)


class TestSimulatorRegistry:
    def test_simresult_metrics_mirror_legacy_fields(self):
        from repro.core import SimConfig, simulate
        from repro.data.synthetic import CTRWorkload

        wl = CTRWorkload(name="zipf", model="wdl",
                         table_sizes=(2_000,) * 4 + (500,) * 8,
                         zipf_a=(1.1,) * 12, hist_max=8, hist_mean=4.0)
        cfg = SimConfig(workload=wl, n_workers=4, batch_per_worker=8,
                        cache_ratio=0.05, embedding_dim=8, iters=4,
                        warmup=1, mechanism="esd", alpha=1.0)
        reg = MetricsRegistry()
        r = simulate(cfg, registry=reg)
        snap = reg.snapshot()
        assert r.metrics == snap
        # legacy fields are reductions of the same registry quantities
        hits = snap["cache.hits"]["value"]
        lookups = snap["cache.lookups"]["value"]
        assert r.hit_ratio == hits / max(lookups, 1)
        assert snap["sim.iter_cost_s"]["count"] == len(r.per_iter_cost)
        assert r.decision_time_mean == pytest.approx(
            snap["dispatch.decision_s"]["mean"], rel=1e-12)

    def test_default_registry_is_fresh_per_call(self):
        from repro.core import SimConfig, simulate
        from repro.data.synthetic import CTRWorkload

        wl = CTRWorkload(name="zipf", model="wdl",
                         table_sizes=(2_000,) * 4 + (500,) * 8,
                         zipf_a=(1.1,) * 12, hist_max=8, hist_mean=4.0)
        cfg = SimConfig(workload=wl, n_workers=4, batch_per_worker=8,
                        cache_ratio=0.05, embedding_dim=8, iters=3,
                        warmup=1, mechanism="esd", alpha=1.0)
        a, b = simulate(cfg), simulate(cfg)
        assert a.metrics == b.metrics         # no cross-run accumulation


# ------------------------------------------------------------- log_step

class TestLogStep:
    def test_stable_key_order(self):
        buf = io.StringIO()
        line = log_step({"wall_s": 0.1, "cost": 2.0, "loss": 0.5,
                         "step": 3, "alg1_est": 1.0}, stream=buf)
        assert buf.getvalue() == line + "\n"
        assert list(json.loads(line)) == ["step", "loss", "wall_s",
                                          "alg1_est", "cost"]

    def test_defaults_to_stderr(self, capsys):
        log_step({"step": 0, "loss": 1.0})
        cap = capsys.readouterr()
        assert cap.out == ""
        assert json.loads(cap.err) == {"step": 0, "loss": 1.0}


# ------------------------------------------------------ schema + writer

class TestSchema:
    def test_gate_ops(self):
        doc = {"a": 2.0, "b": [{"v": 1.0}, {"v": 3.0}], "flag": True}
        ok = [Gate("a", "ge", 2.0), Gate("a", "le", 2.0),
              Gate("a", "in_range", (1.0, 3.0)), Gate("a", "eq", 2.0),
              Gate("b[*].v", "gt", 0.0), Gate("flag", "is_true")]
        errors: list = []
        for g in ok:
            _check_gate(doc, g, errors)
        assert errors == []
        bad: list = []
        _check_gate(doc, Gate("b[*].v", "ge", 2.0), bad)
        assert len(bad) == 1 and "b[0].v" in bad[0]

    def test_missing_required_vs_optional(self):
        errors: list = []
        _check_gate({}, Gate("nope", "ge", 0.0), errors)
        assert errors and "missing" in errors[0]
        errors = []
        _check_gate({}, Gate("nope", "ge", 0.0, required=False), errors)
        assert errors == []

    def test_nan_rejected_anywhere(self):
        errors: list = []
        _sweep_finite({"deep": [{"x": math.nan}]}, "", errors)
        assert errors and "deep[0].x" in errors[0]
        with pytest.raises(SchemaError, match="non-finite"):
            validate_bench("dispatch", {
                "results": [{"V": 1, "jit": {"sparse_ms": 1.0},
                             "numpy": {"sparse_ms": float("inf")}}]})

    def test_bool_is_not_a_number(self):
        errors: list = []
        _check_gate({"x": True}, Gate("x", "ge", 0.0), errors)
        assert errors and "not a finite number" in errors[0]

    def test_bench_name_from_path(self):
        assert bench_name_from_path("BENCH_quant.json") == "quant"
        assert bench_name_from_path("/a/b/BENCH_quant_quick.json") == "quant"
        assert bench_name_from_path("BENCH_multips_quick.json") == "multips"
        assert bench_name_from_path("notes.json") is None

    def test_validate_bench_reports_all_violations(self):
        with pytest.raises(SchemaError) as e:
            validate_bench("quant", {
                "results": {"fp32": {"final_loss": 50.0},
                            "int8": {"quant": {"byte_reduction": 1.0}}}})
        msg = str(e.value)
        assert ("results.fp32.final_loss" in msg
                and "results.int8.quant.byte_reduction" in msg)


class TestWriteBench:
    GOOD = {"results": {"fp32": {"final_loss": 0.5},
                        "int8": {"quant": {"byte_reduction": 4.0}}}}

    def test_writes_canonical_and_quick_paths(self, tmp_path):
        p = write_bench("quant", self.GOOD, results_dir=tmp_path)
        assert p == tmp_path / "BENCH_quant.json"
        q = write_bench("quant", self.GOOD, quick=True, results_dir=tmp_path)
        assert q == tmp_path / "BENCH_quant_quick.json"
        assert json.loads(p.read_text()) == self.GOOD
        assert not list(tmp_path.glob("*.tmp"))   # atomic: no leftovers

    def test_out_override(self, tmp_path):
        p = write_bench("quant", self.GOOD, out=tmp_path / "x.json")
        assert p == tmp_path / "x.json" and p.exists()

    def test_invalid_report_never_touches_disk(self, tmp_path):
        bad = {"results": {"fp32": {"final_loss": 50.0},
                           "int8": {"quant": {"byte_reduction": 1.0}}}}
        with pytest.raises(SchemaError):
            write_bench("quant", bad, results_dir=tmp_path)
        assert list(tmp_path.iterdir()) == []

    def test_mirrors_gauges_into_registry(self, tmp_path):
        with use_registry() as reg:
            write_bench("quant", self.GOOD, results_dir=tmp_path)
        assert reg.value("bench.quant.results.fp32.final_loss") == 0.5
        assert reg.value(
            "bench.quant.results.int8.quant.byte_reduction") == 4.0


# ------------------------------------------------- driver integration

@pytest.mark.slow
class TestDriverRegistry:
    def test_driver_steps_are_registry_view(self):
        from repro.launch.train import main
        from repro.obs import get_registry

        metrics = main(["--arch", "wdl-tiny", "--steps", "3",
                        "--batch-per-worker", "8", "--esd-alpha", "1"])
        reg = get_registry()
        assert reg.steps is metrics
        assert reg.value("train.loss") == metrics[-1]["loss"]
        assert reg.value("cache.miss_pull") == sum(
            m["miss_pull"] for m in metrics)
