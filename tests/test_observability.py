"""Telemetry subsystem: spans, metrics, probe, report, engine wiring.

The ISSUE-6 acceptance criteria, as tests:

  * the span stack nests/restores correctly under exceptions, concurrent
    threads, and interleaved asyncio tasks (the same contextvar
    discipline ``test_execution.py`` proves for ``ExecutionContext``);
  * the trace buffer is bounded (oldest events drop, counted) and both
    export formats round-trip through the report CLI;
  * the metrics registry validates names/labels, registers idempotently,
    and renders well-formed Prometheus text exposition;
  * the step-time probe is inert while observability is off (off-is-free)
    and, when active, reports per-pod times proportional to the units
    each pod ran — occupancy cancels in the scheduler's rate;
  * a traced engine run emits the engine's step and admission spans
    (to the buffer, and to the profiler's trace on the device's clock)
    and the engine metric families, and ``EngineStats.snapshot()`` is the
    one JSON reporting surface;
  * the calibration loop CLOSES: with the probe measuring real wall
    times that contradict the typed big:little ratio, the dynamic
    scheduler drifts and re-derives the chunk table — a rebalance driven
    entirely by *measured* signal, visible in the trace.
"""

import json
import threading
import time

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core.asymmetric import AsymmetricMesh, biglittle_classes
from repro.models import model_zoo as Z
from repro.observability import metrics as MET
from repro.observability import report, trace as T
from repro.observability.probe import StepTimeProbe
from repro.runtime.serving import ServingEngine


@pytest.fixture(autouse=True)
def _trace_off():
    """Every test starts and ends with tracing disabled (module switch)."""

    T.disable()
    yield
    T.disable()


def _biglittle(**kw):
    kw.setdefault("strategy", "ca-das")
    kw.setdefault("batch_tile", 1)
    return AsymmetricMesh(biglittle_classes(chips_per_pod=1), **kw)


# ---------------------------------------------------------------------------
# Span stack: nesting, exceptions, threads, asyncio
# ---------------------------------------------------------------------------


class TestSpans:
    def test_disabled_is_noop_singleton(self):
        # Off-is-free: no allocation, no state — the same reusable object.
        s1, s2 = T.span("a"), T.span("b")
        assert s1 is s2
        with s1 as s:
            assert s.tag(x=1) is s  # tag() chains harmlessly
        assert T.current_span() is None

    def test_nesting_and_parent_attribution(self):
        buf = T.enable(capacity=64)
        with T.span("outer", cat="test"):
            assert T.current_span().name == "outer"
            with T.span("inner", cat="test", device_class="big") as sp:
                assert T.current_span() is sp
                sp.tag(block_source="tuned")
            assert T.current_span().name == "outer"
        assert T.current_span() is None

        by_name = {e.name: e for e in buf.events}
        assert by_name["inner"].parent == "outer"
        assert by_name["outer"].parent is None
        assert by_name["inner"].args["device_class"] == "big"
        assert by_name["inner"].args["block_source"] == "tuned"
        # inner closed first, so it is recorded first; both are complete
        # events with non-negative durations nested inside the outer.
        assert [e.name for e in buf.events] == ["inner", "outer"]
        assert all(e.ph == "X" and e.dur >= 0.0 for e in buf.events)
        inner, outer = by_name["inner"], by_name["outer"]
        assert outer.ts <= inner.ts
        assert inner.ts + inner.dur <= outer.ts + outer.dur + 1e-6

    def test_exception_restores_stack_and_tags_error(self):
        buf = T.enable()
        with pytest.raises(RuntimeError):
            with T.span("boom"):
                raise RuntimeError("x")
        assert T.current_span() is None
        (ev,) = buf.events
        assert ev.args["error"] == "RuntimeError"

    def test_misnested_exit_drops_only_self(self):
        # Out-of-order exit (possible with manual enter/exit) must not
        # corrupt the rest of the stack.
        T.enable()
        a = T.span("a").__enter__()
        b = T.span("b").__enter__()
        a.__exit__(None, None, None)
        assert T.current_span() is b
        b.__exit__(None, None, None)
        assert T.current_span() is None

    def test_concurrent_threads_have_independent_stacks(self):
        # Mirrors test_execution.TestContextScoping: each thread starts
        # from the default empty stack, so nesting in one thread is
        # invisible to — and unpoppable by — another.
        buf = T.enable(capacity=4096)
        errors = []

        def worker(tag):
            try:
                for _ in range(25):
                    with T.span(f"outer-{tag}"):
                        with T.span(f"inner-{tag}") as sp:
                            assert T.current_span() is sp
                        assert T.current_span().name == f"outer-{tag}"
                    assert T.current_span() is None
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        # Every inner span's parent is its own thread's outer span.
        for ev in buf.events:
            if ev.name.startswith("inner-"):
                tag = ev.name.split("-", 1)[1]
                assert ev.parent == f"outer-{tag}"

    def test_interleaved_async_tasks_have_independent_stacks(self):
        # Two asyncio tasks on one thread hold spans in interleaved
        # order; each task runs in a copied context, so neither sees
        # (or pops) the other's stack.
        import asyncio

        buf = T.enable()

        async def main():
            a_in, b_in = asyncio.Event(), asyncio.Event()

            async def task_a():
                with T.span("task-a"):
                    a_in.set()
                    await b_in.wait()  # b enters while a is inside
                    assert T.current_span().name == "task-a"
                    with T.span("child-a"):
                        pass
                assert T.current_span() is None

            async def task_b():
                await a_in.wait()
                assert T.current_span() is None  # a's span is not visible
                with T.span("task-b"):
                    b_in.set()
                    assert T.current_span().name == "task-b"
                    with T.span("child-b"):
                        pass
                assert T.current_span() is None

            await asyncio.gather(task_a(), task_b())

        asyncio.run(main())
        parents = {e.name: e.parent for e in buf.events}
        assert parents["child-a"] == "task-a"
        assert parents["child-b"] == "task-b"
        assert parents["task-a"] is None
        assert parents["task-b"] is None


# ---------------------------------------------------------------------------
# Buffer bounding + export formats + report CLI round-trip
# ---------------------------------------------------------------------------


class TestBufferAndExport:
    def test_capacity_bounds_and_counts_drops(self):
        buf = T.enable(capacity=4)
        for i in range(7):
            T.instant(f"ev{i}")
        assert len(buf) == 4
        assert buf.dropped == 3
        assert [e.name for e in buf.events] == ["ev3", "ev4", "ev5", "ev6"]
        buf.clear()
        assert len(buf) == 0 and buf.dropped == 0

    def test_enable_is_idempotent_disable_detaches(self):
        buf = T.enable()
        assert T.enable() is buf  # existing buffer kept
        T.instant("x")
        detached = T.disable()
        assert detached is buf and not T.enabled()
        T.instant("after")  # silently dropped: no buffer
        assert [e.name for e in detached.events] == ["x"]

    def test_chrome_trace_structure(self):
        T.enable()
        t0 = time.perf_counter()
        with T.span("outer"):
            T.instant("mark", note="hi")
        T.complete("posthoc", t0, 0.002, device_class="little")
        T.counter("queue", big=3, little=1)
        buf = T.disable()

        chrome = buf.chrome_trace()
        evs = {e["name"]: e for e in chrome["traceEvents"]}
        assert chrome["displayTimeUnit"] == "ms"
        assert evs["outer"]["ph"] == "X" and "dur" in evs["outer"]
        assert evs["mark"]["ph"] == "i" and evs["mark"]["s"] == "t"
        assert evs["mark"]["args"]["parent"] == "outer"
        assert evs["posthoc"]["dur"] == pytest.approx(2000.0, rel=1e-3)  # µs
        assert evs["queue"]["ph"] == "C" and evs["queue"]["args"] == {
            "big": 3, "little": 1,
        }
        json.dumps(chrome)  # must be serializable as-is

    def test_save_load_roundtrip_both_formats(self, tmp_path):
        T.enable()
        with T.span("work", device_class="big"):
            T.instant("tick")
        buf = T.disable()
        native = tmp_path / "trace.json"
        chrome = tmp_path / "chrome.json"
        buf.save(str(native))
        buf.export_chrome_trace(str(chrome))

        ev_n, meta_n = report.load_events(str(native))
        ev_c, meta_c = report.load_events(str(chrome))
        assert meta_n["format"] == "native" and meta_c["format"] == "chrome"
        assert {e["name"] for e in ev_n} == {e["name"] for e in ev_c} == {
            "work", "tick",
        }
        # Chrome stores µs; load_events normalizes back to seconds.
        wn = next(e for e in ev_n if e["name"] == "work")
        wc = next(e for e in ev_c if e["name"] == "work")
        assert wc["dur"] == pytest.approx(wn["dur"], rel=1e-3)
        with pytest.raises(ValueError):
            bad = tmp_path / "bad.json"
            bad.write_text("[1, 2]")
            report.load_events(str(bad))

    def test_truncated_native_trace_is_salvaged(self, tmp_path):
        # The ISSUE-10 robustness contract: a trace torn mid-write (killed
        # engine, full disk) degrades to a salvage scan — every record
        # that still parses is kept, the torn tail is counted, and the
        # loader never raises.
        T.enable()
        for i in range(6):
            T.instant(f"ev{i}", k=i)
        buf = T.disable()
        p = tmp_path / "t.json"
        buf.save(str(p))
        text = p.read_text()
        # Tear the file inside the LAST event record (native format sorts
        # keys, so "events" is the final array in the file).
        p.write_text(text[: text.rfind("{") + 8])

        events, meta = report.load_events(str(p))
        assert meta["format"] == "native"
        assert meta["skipped_records"] >= 1
        names = [e["name"] for e in events]
        assert names == [f"ev{i}" for i in range(5)]  # all but the torn one
        assert events[0]["args"] == {"k": 0}

    def test_truncated_chrome_trace_is_salvaged(self, tmp_path):
        T.enable()
        with T.span("work"):
            T.instant("tick")
        buf = T.disable()
        p = tmp_path / "c.json"
        buf.export_chrome_trace(str(p))
        text = p.read_text()
        # Tear the file inside the last record ("work" closes after the
        # instant, so it serializes last).
        p.write_text(text[: text.rfind('"name": "work"') + 8])

        events, meta = report.load_events(str(p))
        assert meta["format"] == "chrome"
        assert meta["skipped_records"] >= 1
        assert [e["name"] for e in events] == ["tick"]  # "work" record torn

    def test_clean_trace_reports_zero_skipped(self, tmp_path):
        T.enable()
        T.instant("x")
        T.disable().save(str(tmp_path / "t.json"))
        _, meta = report.load_events(str(tmp_path / "t.json"))
        assert meta["skipped_records"] == 0

    def test_report_cli_warns_on_corrupt_trace(self, tmp_path, capsys):
        # The CLI survives the damaged file and says so in the header —
        # the post-mortem tool must not die of the kill it reports on.
        T.enable()
        for i in range(4):
            T.instant(f"ev{i}")
        buf = T.disable()
        p = tmp_path / "t.json"
        buf.save(str(p))
        text = p.read_text()
        p.write_text(text[: text.rfind("{") + 8])

        rc = report.main([str(p)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "WARNING" in out and "skipped" in out
        assert "ev0" in out

    def test_report_cli_main(self, tmp_path, capsys):
        T.enable()
        with T.span("engine.step"):
            pass
        T.instant("scheduler.rebalance")
        T.disable().save(str(tmp_path / "t.json"))
        out_chrome = tmp_path / "c.json"
        rc = report.main([str(tmp_path / "t.json"), "--chrome", str(out_chrome)])
        assert rc == 0
        text = capsys.readouterr().out
        assert "engine.step" in text
        assert "scheduler.rebalance" in text
        assert json.loads(out_chrome.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# Metrics registry: validation, idempotence, exposition, snapshot
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter_gauge_histogram_basics(self):
        reg = MET.MetricsRegistry()
        c = reg.counter("req_total", "requests")
        c.inc()
        c.inc(2.5)
        with pytest.raises(ValueError):
            c.inc(-1)
        g = reg.gauge("depth", "queue depth")
        g.set(4)
        g.inc()
        g.dec(2)
        h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["req_total"]["samples"][0]["value"] == 3.5
        assert snap["depth"]["samples"][0]["value"] == 3.0
        hs = snap["lat_seconds"]["samples"][0]
        assert hs["count"] == 4
        assert hs["sum"] == pytest.approx(5.555)
        # Cumulative buckets: one observation per band, +Inf == count.
        assert hs["buckets"] == {"0.01": 1, "0.1": 2, "1": 3, "+Inf": 4}
        json.dumps(snap)

    def test_label_validation_and_children(self):
        reg = MET.MetricsRegistry()
        fam = reg.counter("adm_total", labels=("device_class",))
        fam.labels(device_class="big").inc(2)
        fam.labels(device_class="little").inc()
        assert fam.labels(device_class="big") is fam.labels(device_class="big")
        with pytest.raises(ValueError):
            fam.labels(wrong="x")  # exact label-name set required
        with pytest.raises(ValueError):
            fam.inc()  # labeled family has no default child
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            reg.counter("ok", labels=("bad-label",))

    def test_idempotent_reregistration_and_mismatch(self):
        reg = MET.MetricsRegistry()
        a = reg.counter("x_total", "help", labels=("k",))
        assert reg.counter("x_total", "other help", labels=("k",)) is a
        with pytest.raises(ValueError):
            reg.gauge("x_total")  # kind mismatch
        with pytest.raises(ValueError):
            reg.counter("x_total", labels=("other",))  # label mismatch

    def test_prometheus_exposition_format(self):
        reg = MET.MetricsRegistry()
        c = reg.counter("req_total", "requests served", labels=("cls",))
        c.labels(cls='wei"rd\\v').inc(3)
        h = reg.histogram("step_seconds", "step time", buckets=(0.5,))
        h.observe(0.25)
        h.observe(2.0)
        text = reg.exposition()
        lines = text.splitlines()
        assert "# HELP req_total requests served" in lines
        assert "# TYPE req_total counter" in lines
        assert 'req_total{cls="wei\\"rd\\\\v"} 3' in lines
        assert "# TYPE step_seconds histogram" in lines
        assert 'step_seconds_bucket{le="0.5"} 1' in lines
        assert 'step_seconds_bucket{le="+Inf"} 2' in lines
        assert "step_seconds_sum 2.25" in lines
        assert "step_seconds_count 2" in lines
        assert text.endswith("\n")


# ---------------------------------------------------------------------------
# Step-time probe: inert when off, measured per-pod times when on
# ---------------------------------------------------------------------------


class TestStepTimeProbe:
    def test_inert_while_observability_disabled(self):
        probe = StepTimeProbe(_biglittle())
        assert not probe.active()
        assert probe(0, [1, 1]) is None
        assert probe.refreshes == 0  # zero work: off-is-free

    def test_measured_times_scale_with_units(self):
        # Deterministic workloads (sleeps) stand in for the probe GEMM:
        # still wall-clock measured under each class's context, but with
        # a controlled skew — little "measures" ~4x slower than big.
        asym = _biglittle()
        probe = StepTimeProbe(
            asym, interval=64, reps=1, probe_shape=(100, 128, 128),
            workloads={
                "big": lambda: time.sleep(0.002),
                "little": lambda: time.sleep(0.008),
            },
            always=True,
        )
        times = probe(0, [4, 2])
        assert probe.refreshes == 1
        assert len(times) == asym.n_pods
        # times[pod] = units * row_seconds[class]: occupancy is explicit,
        # so observe()'s rate u/(u*s) reduces to pure class speed.
        rs_big = probe.last_measured["big"] / 100
        rs_little = probe.last_measured["little"] / 100
        assert times[0] == pytest.approx(4 * rs_big)
        assert times[1] == pytest.approx(2 * rs_little)
        assert rs_little > rs_big
        # Zero units -> zero charged time (pod idle this step).
        assert probe(1, [0, 3])[0] == 0.0
        # Within the interval no re-measurement happens...
        assert probe.refreshes == 1
        # ...but an interval boundary refreshes.
        probe(64, [1, 1])
        assert probe.refreshes == 2
        # The refresh published per-class gauges to the global registry.
        snap = MET.REGISTRY.snapshot()
        classes = {
            s["labels"]["device_class"]
            for s in snap["probe_row_seconds"]["samples"]
        }
        assert {"big", "little"} <= classes

    def test_default_unit_charge_is_one_per_pod(self):
        probe = StepTimeProbe(
            _biglittle(), reps=1,
            workloads={"big": lambda: None, "little": lambda: None},
            always=True,
        )
        times = probe(0)
        assert len(times) == 2 and all(t >= 0.0 for t in times)


# ---------------------------------------------------------------------------
# Engine wiring: traced run emits class-tagged spans + metric families
# ---------------------------------------------------------------------------


ARCH = "internlm2-1.8b"


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config(ARCH).reduced()
    return cfg, Z.init_params(jax.random.PRNGKey(0), cfg)


class TestEngineTelemetry:
    def test_snapshot_is_the_reporting_surface(self, small_model):
        cfg, params = small_model
        eng = ServingEngine(
            cfg, params, _biglittle(), seq_cap=24, slots_per_pod=4,
            class_sharded="off", pod_time_hook=None,
        )
        snap = eng.stats.snapshot()
        json.dumps(snap)
        # Every dataclass field plus the derived throughput/efficiency
        # metrics, nothing hand-mirrored: new fields show up here
        # automatically.
        import dataclasses as dc

        assert set(snap) == {f.name for f in dc.fields(eng.stats)} | {
            "tokens_per_s", "tokens_per_j", "modeled_tokens_per_s"
        }

    def test_traced_generate_emits_spans_and_metrics(self, small_model):
        cfg, params = small_model
        eng = ServingEngine(
            cfg, params, _biglittle(), seq_cap=24, slots_per_pod=4,
            class_sharded="off", pod_time_hook=None,
        )
        prompts = np.asarray(
            np.random.default_rng(3).integers(0, cfg.vocab, (4, 4)), np.int32
        )
        T.enable()
        try:
            eng.generate(prompts, 4)
        finally:
            buf = T.disable()

        names = [e.name for e in buf.events]
        assert "engine.admit" in names
        steps = [e for e in buf.events if e.name == "engine.step"]
        assert len(steps) >= 3
        # One SPMD step, one span: its args say how many slot-table rows
        # the program ran over and how many of them were live.
        assert [e.args["step"] for e in steps] == list(range(len(steps)))
        assert all(e.args["rows"] == eng.n_slots for e in steps)
        assert steps[0].args["active"] == 4
        assert all(0 < e.args["active"] <= e.args["rows"] for e in steps)
        assert {e.name for e in buf.events if e.parent == "engine.step"} == {
            "engine.step.inputs", "engine.step.launch", "engine.step.wait",
            "engine.step.retire", "engine.step.calibrate",
        }

        snap = MET.REGISTRY.snapshot()
        for key in (
            "engine_queue_depth", "engine_slot_occupancy",
            "engine_admissions_total", "engine_tokens_total",
            "engine_decode_step_seconds",
        ):
            assert key in snap, key
        assert "engine_tokens_per_s" not in snap and "engine_modeled_watts" not in snap
        adm = {
            s["labels"]["device_class"]: s["value"]
            for s in snap["engine_admissions_total"]["samples"]
        }
        assert sum(adm.values()) >= 4  # every admitted request counted

    def test_untraced_generate_records_nothing(self, small_model):
        # The off-is-free contract at the engine level: no buffer, no
        # events, hook inert — generate() behaves exactly as before.
        cfg, params = small_model
        eng = ServingEngine(
            cfg, params, _biglittle(), seq_cap=24, slots_per_pod=4,
            class_sharded="off",  # default "auto" probe, tracing off
        )
        prompts = np.asarray(
            np.random.default_rng(4).integers(0, cfg.vocab, (4, 4)), np.int32
        )
        out = eng.generate(prompts, 4)
        assert out.shape == (4, 8)
        assert not T.enabled()
        assert isinstance(eng.pod_time_hook, StepTimeProbe)
        assert eng.pod_time_hook.refreshes == 0  # probe never fired
        # Calibration stayed frozen at the typed ratios.
        rates = eng.asym.scheduler.rates
        assert rates[0] == pytest.approx(1.0) and rates[1] == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# The profiler sink: engine spans on the device trace's clock
# ---------------------------------------------------------------------------


def _host_spans(trace_dir, prefixes=("engine.", "host.", "test.")):
    """``[(name, start_ns, end_ns, stats)]`` of the host plane's events
    whose names start with ``prefixes``, from a profiler session's
    ``.xplane.pb``, in start order."""

    import glob

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _children(spans, parent):
    """The spans strictly inside ``parent``'s interval, in start order."""

    _, s0, e0, _ = parent
    return [sp for sp in spans if sp is not parent and s0 <= sp[1] and sp[2] <= e0]


class TestProfilerSink:
    def test_profiler_session_records_spans_with_args(self, tmp_path):
        # Buffer off: outside a profiler session the span is the no-op;
        # inside one it is real, and its args (also those tagged late, and
        # the error class of a failing body) become the event's metadata.
        assert T.span("test.a") is T.span("test.b")
        with jax.profiler.trace(str(tmp_path)):
            sp = T.span("test.outer", step=3)
            assert sp is not T.span("test.b")
            with sp:
                sp.tag(compiles=1)
                with pytest.raises(KeyError):
                    with T.span("test.inner"):
                        raise KeyError("x")
        assert T.span("test.a") is T.span("test.b")
        assert not T.enabled() and len(T._STACK.get()) == 0
        (outer, inner) = _host_spans(tmp_path, ("test.",))
        assert outer[0] == "test.outer" and outer[3] == {"step": 3, "compiles": 1}
        assert inner[0] == "test.inner" and inner[3] == {"error": "KeyError"}
        assert outer[1] <= inner[1] and inner[2] <= outer[2]

    def test_engine_spans_nest_on_the_profiler_clock(self, small_model, tmp_path):
        cfg, params = small_model
        eng = ServingEngine(
            cfg, params, _biglittle(), seq_cap=24, slots_per_pod=4,
            class_sharded="off", pod_time_hook=None, paged="on", page_size=8,
        )
        prompts = np.asarray(
            np.random.default_rng(6).integers(0, cfg.vocab, (3, 5)), np.int32
        )
        with jax.profiler.trace(str(tmp_path)):
            eng.generate(prompts, 3)
        spans = _host_spans(tmp_path, ("engine.",))

        steps = [sp for sp in spans if sp[0] == "engine.step"]
        assert len(steps) == 2
        for i, step in enumerate(steps):
            assert step[3]["step"] == i
            assert step[3]["active"] == 3 and step[3]["rows"] == eng.n_slots
            assert step[3]["compiles"] == (1 if i == 0 else 0)
            kids = _children(spans, step)
            assert [k[0] for k in kids] == [
                "engine.step.inputs", "engine.step.launch", "engine.step.wait",
                "engine.step.retire", "engine.step.calibrate",
            ]
            # One after another, on one clock.
            assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))

        (admit,) = [sp for sp in spans if sp[0] == "engine.admit"]
        assert admit[3]["admitted"] == 3 and admit[3]["round_len"] == 5
        assert admit[3]["compiles"] >= 1
        assert [k[0] for k in _children(spans, admit)] == [
            "engine.admit.route", "engine.admit.inputs", "engine.admit.launch",
            "engine.admit.wait", "engine.admit.retire",
        ]
        assert admit[2] <= steps[0][1]

    def test_gc_collection_is_named_in_the_trace(self, tmp_path):
        import gc

        with jax.profiler.trace(str(tmp_path)):
            with T.span("test.outer"):
                gc.collect()
        spans = _host_spans(tmp_path)
        outer = next(sp for sp in spans if sp[0] == "test.outer")
        collections = [sp for sp in _children(spans, outer) if sp[0] == "host.gc"]
        assert collections and collections[-1][3]["generation"] == 2
        assert "collected" in collections[-1][3]


class TestEngineAccounting:
    def test_probe_stays_inert_under_tracing(self):
        # Tracing on does not arm the default probe: no probe GEMM may run
        # inside a traced window.  Only always=True measures.
        T.enable()
        probe = StepTimeProbe(
            _biglittle(), reps=1,
            workloads={"big": lambda: None, "little": lambda: None},
        )
        assert not probe.active()
        assert probe(0, [1, 1]) is None
        assert probe.refreshes == 0
        armed = StepTimeProbe(
            _biglittle(), reps=1,
            workloads={"big": lambda: None, "little": lambda: None},
            always=True,
        )
        assert armed(0, [1, 1]) is not None and armed.refreshes == 1

    def test_compile_counter_counts_only_new_programs(self, small_model):
        # A prefill length compiles once; its second admission compiles
        # nothing, and its time goes to prefill_s, not compile_s.
        cfg, params = small_model
        eng = ServingEngine(
            cfg, params, _biglittle(), seq_cap=24, slots_per_pod=4,
            class_sharded="off", pod_time_hook=None,
        )
        rng = np.random.default_rng(7)
        T.enable()
        try:
            counts = []
            for _ in range(2):
                eng.submit(rng.integers(0, cfg.vocab, 6), 1)
                before = eng.stats.compiles, eng.stats.compile_s, eng.stats.prefill_s
                assert eng.admit() == 1
                counts.append(eng.stats.compiles - before[0])
        finally:
            buf = T.disable()
        assert counts[0] >= 1 and counts[1] == 0
        assert eng.stats.compile_s - before[1] == 0.0
        assert eng.stats.prefill_s > before[2] > 0.0
        admits = [e for e in buf.events if e.name == "engine.admit"]
        assert [e.args["compiles"] for e in admits] == counts

    def test_queue_wait_is_observed_at_admission(self, small_model):
        cfg, params = small_model
        eng = ServingEngine(
            cfg, params, _biglittle(), seq_cap=24, slots_per_pod=4,
            class_sharded="off", pod_time_hook=None,
        )
        rng = np.random.default_rng(8)

        def waits():
            fam = MET.REGISTRY.snapshot().get("engine_queue_wait_seconds")
            return sum(s["count"] for s in fam["samples"]) if fam else 0

        T.enable()
        try:
            before = waits()
            rids = [eng.submit(rng.integers(0, cfg.vocab, 4), 2) for _ in range(3)]
            time.sleep(0.02)
            assert eng.admit() == 3
        finally:
            buf = T.disable()
        assert waits() == before + 3
        (admit,) = [e for e in buf.events if e.name == "engine.admit"]
        assert admit.args["rids"] == rids
        assert admit.args["queue_wait_max_s"] >= 0.02


# ---------------------------------------------------------------------------
# The loop closes: measured probe times drive a real rebalance
# ---------------------------------------------------------------------------


class TestCalibrationLoopCloses:
    def test_measured_times_trigger_rebalance(self, small_model):
        """Typed ratios say big:little = 4:1, but the probe *measures* the
        opposite skew — so the scheduler must drift off its initial table
        and re-derive the chunk sizes from the measured signal.  This is
        the feedback path PR 5 left open (no fabricated equal-times): the
        probe closes it with honest wall-clock data."""

        cfg, params = small_model
        asym = _biglittle()  # typed init: rates [1.0, 0.25]
        probe = StepTimeProbe(
            asym, interval=4, reps=1, probe_shape=(100, 128, 128),
            # Measured truth contradicts the typed ratio: little is ~4x
            # FASTER than big.  (Sleeps keep the skew deterministic while
            # the probe still takes real wall-clock measurements.)
            workloads={
                "big": lambda: time.sleep(0.004),
                "little": lambda: time.sleep(0.001),
            },
            always=True,
        )
        eng = ServingEngine(
            cfg, params, asym, seq_cap=24, slots_per_pod=8,
            class_sharded="off", pod_time_hook=probe,
        )
        prompts = np.asarray(
            np.random.default_rng(5).integers(0, cfg.vocab, (8, 4)), np.int32
        )

        T.enable()
        try:
            # First wave: the routing table derives from the typed 4:1
            # ratios; decode steps feed measured times into observe().
            eng.generate(prompts, 4)
            sched = asym.scheduler
            assert probe.refreshes >= 1
            # Measured rates inverted the typed ordering...
            assert sched.rates[1] > sched.rates[0]
            # ...far past the hysteresis threshold.
            assert sched.needs_rebalance()
            before = list(sched._last_sizes)

            # Second wave re-routes the same batch size: same n_units, so
            # the re-derivation counts as a rebalance and flips the split
            # toward the measured-faster class.
            eng.generate(prompts, 4)
        finally:
            buf = T.disable()

        after = list(asym.scheduler._last_sizes)
        assert eng.stats.rebalances >= 1
        assert after != before
        assert after[1] > before[1]  # little (measured faster) gained units

        # The rebalance is visible in the trace, with its trigger drift
        # and the before/after chunk sizes.
        rebs = [e for e in buf.events if e.name == "scheduler.rebalance"]
        assert rebs, [e.name for e in buf.events]
        ev = rebs[0].args
        assert ev["drift"] > ev["threshold"]
        assert ev["before"] == before and sum(ev["after"]) == sum(before)
        assert any(e.name == "probe.measured" for e in buf.events)


# ---------------------------------------------------------------------------
# Tuning + harness telemetry satellites
# ---------------------------------------------------------------------------


class TestTuningTelemetry:
    def test_search_emits_span_and_candidate_timings(self):
        from repro.core.blocking import TPU_V5E
        from repro.tuning.tune import _obs_metrics, tune_shapes

        misses0 = _obs_metrics()["cache"].labels(result="miss").value
        T.enable()
        try:
            (res,) = tune_shapes(
                [(512, 512, 512)], spec=TPU_V5E, backend_name="cost-model",
            )
        finally:
            buf = T.disable()
        spans = {e.name: e for e in buf.events}
        search = spans["tuning.search_shape"]
        assert search.args["n_candidates"] == res.n_candidates
        assert search.args["best"] == [res.best.bm, res.best.bk, res.best.bn]
        cands = [e for e in buf.events if e.name == "tuning.candidate"]
        assert len(cands) == res.n_candidates
        assert all(e.parent == "tuning.search_shape" for e in cands)
        snap = MET.REGISTRY.snapshot()
        assert snap["tuning_candidate_seconds"]["samples"][0]["count"] >= len(cands)
        # The uncached shape counted as a lookup miss.
        assert _obs_metrics()["cache"].labels(result="miss").value == misses0 + 1


class TestHarnessMetadata:
    def test_run_metadata_fields(self):
        from benchmarks.harness import run_metadata

        meta = run_metadata(bench="x", spec="tpu-v5e")
        assert meta["bench"] == "x" and meta["spec"] == "tpu-v5e"
        assert "timestamp" in meta and "jax_version" in meta and "git_sha" in meta
        assert meta["jax_version"] == jax.__version__

    def test_write_json_stamps_meta_and_compare_ignores_it(self, tmp_path):
        from benchmarks.harness import compare_records, load_records, write_json

        records = [{"impl": "xla", "us_per_call": 12.5}]
        p1 = write_json(str(tmp_path / "a.json"), records, bench="t")
        p2 = write_json(str(tmp_path / "b.json"), records, bench="t")
        data = json.loads(open(p1).read())
        assert set(data) == {"meta", "records"}
        assert data["meta"]["bench"] == "t"
        assert load_records(p1) == records
        # Differing meta (timestamps), identical records: no diff.
        assert compare_records(p1, p2) == []
        # A record change IS a diff, named by key.
        write_json(str(tmp_path / "c.json"), [{"impl": "xla", "us_per_call": 13.0}])
        diffs = compare_records(p1, str(tmp_path / "c.json"))
        assert diffs and "us_per_call" in diffs[0]

    def test_load_records_tolerates_legacy_bare_list(self, tmp_path):
        from benchmarks.harness import load_records

        p = tmp_path / "old.json"
        p.write_text('[{"impl": "xla"}]')
        assert load_records(str(p)) == [{"impl": "xla"}]
