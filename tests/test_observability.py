"""Tests for the unified telemetry stack (`repro.obs`).

Pins the PR-9 contracts: span nesting/ordering under an injected virtual
clock, flight-ring overflow semantics, the no-op tracer's bitwise
non-interference with a pinned serve run, and the Perfetto JSON schema
round-trip.
"""
import json

import jax
import numpy as np
import pytest

from repro.cluster import SliceSpec
from repro.configs import registry
from repro.models import api
from repro.obs import (NOOP_TRACER, FlightRecorder, MetricsRegistry,
                       NoopTracer, Telemetry, Tracer, VirtualClock,
                       from_chrome_trace, to_chrome_trace)


@pytest.fixture(scope="module")
def small_model():
    cfg = registry.get_reduced("olmo-1b")
    return cfg, api.init_params(cfg, jax.random.PRNGKey(0))


# -- tracer: nesting and ordering on a virtual clock --------------------------

class TestTracerVirtualClock:
    def test_span_nesting_parent_ids(self):
        clk = VirtualClock()
        tr = Tracer(clk)
        with tr.span("outer", track="t") as outer:
            clk.advance(1.0)
            with tr.span("inner", track="t") as inner:
                clk.advance(2.0)
        assert inner.parent == outer.sid
        assert outer.parent is None
        # children close first, record order follows completion order
        assert [s.name for s in tr.spans] == ["inner", "outer"]
        assert (outer.t0, outer.t1) == (0.0, 2.0)
        assert (inner.t0, inner.t1) == (1.0, 2.0)

    def test_nesting_is_per_track(self):
        clk = VirtualClock()
        tr = Tracer(clk)
        a = tr.begin("a", track="track_a")
        b = tr.begin("b", track="track_b")
        assert b.parent is None            # different lane, no nesting
        tr.end(b)
        tr.end(a)

    def test_end_closes_dangling_children(self):
        clk = VirtualClock()
        tr = Tracer(clk)
        outer = tr.begin("outer", track="t")
        clk.advance(1.0)
        tr.begin("leaked", track="t")      # never explicitly ended
        clk.advance(3.0)
        tr.end(outer)
        leaked = tr.find("leaked")[0]
        assert leaked.t1 == outer.t1 == 3.0
        assert not tr.open_spans()

    def test_complete_explicit_timestamps(self):
        tr = Tracer(VirtualClock())
        # virtual-time loops emit these out of order; read side sorts
        tr.complete("chunk", 5.0, 6.0, track="replica:0")
        tr.complete("chunk", 1.0, 2.0, track="replica:0")
        assert [s.t0 for s in tr.find("chunk")] == [5.0, 1.0]

    def test_events_time_ordered_on_read(self):
        tr = Tracer(VirtualClock())
        tr.event("late", t=9.0)
        tr.event("early", t=1.0)
        assert [e.name for e in tr.find_events()] == ["early", "late"]

    def test_retention_bounds_count_drops(self):
        tr = Tracer(VirtualClock(), max_spans=2, max_events=1)
        for i in range(4):
            tr.complete(f"s{i}", 0.0, 1.0)
            tr.event(f"e{i}", t=float(i))
        assert len(tr.spans) == 2 and tr.dropped_spans == 2
        assert len(tr.events) == 1 and tr.dropped_events == 3

    def test_virtual_clock_never_rewinds(self):
        clk = VirtualClock(5.0)
        clk.advance(3.0)
        assert clk() == 5.0
        clk.advance(7.0)
        assert clk() == 7.0


# -- flight recorder: ring overflow and postmortems ---------------------------

class TestFlightRecorder:
    def test_ring_overflow_keeps_newest(self):
        fr = FlightRecorder(capacity=3)
        for i in range(10):
            fr.record("event", f"e{i}", float(i))
        window = fr.snapshot()
        assert [r["name"] for r in window] == ["e7", "e8", "e9"]
        assert fr.total_records == 10
        # seq numbers survive the overflow (no renumbering)
        assert [r["seq"] for r in window] == [7, 8, 9]

    def test_last_n(self):
        fr = FlightRecorder(capacity=5)
        for i in range(5):
            fr.record("event", f"e{i}", float(i))
        assert [r["name"] for r in fr.last(2)] == ["e3", "e4"]
        assert fr.last(0) == []

    def test_postmortem_snapshots_window(self):
        fr = FlightRecorder(capacity=4)
        for i in range(6):
            fr.record("event", f"e{i}", float(i))
        pm = fr.postmortem("drill", t=6.0, job=3)
        assert [r["name"] for r in pm["window"]] == ["e2", "e3", "e4", "e5"]
        assert pm["detail"] == {"job": 3}
        # the snapshot is a copy: later records don't mutate it
        fr.record("event", "after", 7.0)
        assert [r["name"] for r in pm["window"]][-1] == "e5"

    def test_postmortem_cap_counts_drops(self):
        fr = FlightRecorder(capacity=2, max_postmortems=2)
        assert fr.postmortem("a") is not None
        assert fr.postmortem("b") is not None
        assert fr.postmortem("c") is None
        assert len(fr.postmortems) == 2 and fr.postmortems_dropped == 1

    def test_dump_postmortems(self, tmp_path):
        fr = FlightRecorder(capacity=2)
        fr.record("event", "boom", 1.0)
        fr.postmortem("lost", t=1.0)
        path = tmp_path / "pm.json"
        fr.dump_postmortems(str(path))
        data = json.loads(path.read_text())
        assert data["postmortems"][0]["reason"] == "lost"
        assert data["postmortems"][0]["window"][0]["name"] == "boom"


# -- telemetry facade ---------------------------------------------------------

class TestTelemetry:
    def test_event_lands_in_ring_exactly_once_enabled(self):
        obs = Telemetry(tracing=True, clock=VirtualClock())
        obs.event("machine.fail", cat="failure", block=3, t=1.0)
        assert len(obs.tracer.events) == 1
        assert len(obs.recorder.ring) == 1      # mirrored once, not twice

    def test_event_lands_in_ring_when_disabled(self):
        obs = Telemetry(tracing=False)
        obs.event("machine.fail", cat="failure", block=3, t=1.0)
        assert obs.tracer is NOOP_TRACER
        assert [r["name"] for r in obs.recorder.snapshot()] \
            == ["machine.fail"]

    def test_spans_mirror_into_ring(self):
        obs = Telemetry(tracing=True, clock=VirtualClock())
        with obs.span("work", track="t"):
            pass
        assert [r["kind"] for r in obs.recorder.snapshot()] == ["span"]

    def test_noop_default_is_shared_and_inert(self):
        obs = Telemetry()
        assert obs.tracer is NOOP_TRACER
        assert not obs.tracing
        # the no-op tracer hands out one shared null context; the handle's
        # span is the profiler annotation alone, which records nothing here
        assert NOOP_TRACER.span("x") is NOOP_TRACER.span("y")
        ctx = obs.span("anything", n=1)
        assert isinstance(ctx, jax.profiler.TraceAnnotation)
        with ctx:
            pass
        assert NoopTracer.spans == [] and NoopTracer.events == []
        assert len(obs.recorder.ring) == 0

    def test_span_reaches_the_profilers_trace(self, tmp_path):
        """A span lands in the profiler's trace by name, nested in its
        parent, with its args as typed stats; with tracing on it is also
        recorded in memory."""
        obs = Telemetry(tracing=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.span("serve.decode", track="t", live=3, steps=8):
                with obs.span("serve.decode.sync", track="t"):
                    jax.numpy.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        got = {e.name: e for p in
               jax.profiler.ProfileData.from_file(str(path)).planes
               if p.name.startswith("/host") for line in p.lines
               for e in line.events if e.name.startswith("serve.")}
        outer, inner = got["serve.decode"], got["serve.decode.sync"]
        assert dict(outer.stats) == {"live": 3, "steps": 8}
        assert dict(inner.stats) == {}
        assert outer.start_ns <= inner.start_ns
        assert (inner.start_ns + inner.duration_ns
                <= outer.start_ns + outer.duration_ns)
        (rec,) = obs.tracer.find("serve.decode")
        assert rec.args == {"live": 3, "steps": 8}
        assert obs.tracer.find("serve.decode.sync")[0].parent == rec.sid


# -- metrics registry ---------------------------------------------------------

class TestMetricsRegistry:
    def test_get_or_create_identity(self):
        reg = MetricsRegistry()
        c1 = reg.counter("fleet.drops", reason="stranded")
        c2 = reg.counter("fleet.drops", reason="stranded")
        c3 = reg.counter("fleet.drops", reason="wait_queue_full")
        assert c1 is c2 and c1 is not c3
        c1.inc(2)
        assert reg.value("fleet.drops", reason="stranded") == 2

    def test_dump_flat_keys(self):
        reg = MetricsRegistry()
        reg.counter("a.n", k="v").inc()
        reg.gauge("a.g").set(2.5)
        reg.histogram("a.h").observe(1.0)
        d = reg.dump()
        assert d["a.n{k=v}"] == 1
        assert d["a.g"] == 2.5
        assert d["a.h"]["count"] == 1

    def test_histogram_summary(self):
        reg = MetricsRegistry()
        h = reg.histogram("lat")
        for v in range(1, 101):
            h.observe(float(v))
        s = h.summary()
        assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0
        assert 45.0 <= s["p50"] <= 55.0
        assert 90.0 <= s["p95"] <= 100.0

    def test_series_cap_drops_oldest(self):
        reg = MetricsRegistry()
        s = reg.series("train.metrics", cap=4)
        for i in range(6):
            s.append({"step": i})
        assert s.dropped > 0
        assert s.samples[-1]["step"] == 5


# -- no-op non-interference: pinned serve run ---------------------------------

class TestNonInterference:
    def test_serve_tokens_bitwise_equal_with_and_without_obs(
            self, small_model, tmp_path):
        from repro.serve.engine import ServeEngine
        cfg, params = small_model
        spec = SliceSpec(slots=2, max_len=32, prompt_len=8, chunk=4)

        def run(obs):
            rng = np.random.default_rng(7)
            eng = ServeEngine(cfg, params, spec, obs=obs)
            reqs = [eng.submit(rng.integers(0, cfg.vocab_size, size=6,
                                            dtype=np.int32),
                               max_new_tokens=8) for _ in range(3)]
            eng.run(max_steps=100)
            return [list(map(int, r.out_tokens)) for r in reqs]

        base = run(None)
        traced = run(Telemetry(tracing=True, clock=VirtualClock()))
        assert base == traced
        assert all(len(t) == 8 for t in base)
        # and with a profiler session recording every span
        jax.profiler.start_trace(str(tmp_path))
        try:
            profiled = run(Telemetry(tracing=True))
        finally:
            jax.profiler.stop_trace()
        assert profiled == base

    def test_engine_counter_views_match_registry(self, small_model):
        from repro.serve.engine import ServeEngine
        cfg, params = small_model
        obs = Telemetry()
        eng = ServeEngine(cfg, params,
                          SliceSpec(slots=1, max_len=32, prompt_len=8,
                                    chunk=4),
                          obs=obs)
        eng.submit(np.arange(6, dtype=np.int32), max_new_tokens=4)
        eng.run(max_steps=50)
        assert eng.prefill_flops_proxy > 0
        assert eng.prefill_flops_proxy == \
            obs.metrics.value("serve.prefill_flops_proxy")
        assert eng.kv_stats()["prefill_flops_proxy"] \
            == eng.prefill_flops_proxy


# -- Perfetto export round-trip -----------------------------------------------

class TestPerfettoRoundTrip:
    def _tracer(self):
        clk = VirtualClock()
        tr = Tracer(clk)
        tr.complete("chunk", 0.5, 0.75, cat="serve", track="replica:0",
                    stall_s=0.0)
        with tr.span("step", cat="train", track="train", step=3):
            clk.advance(1.25)
        tr.event("fail", cat="failure", track="replica:0", t=2.0, block=4)
        return tr

    def test_schema_shape(self):
        obj = to_chrome_trace(self._tracer(), process_name="p",
                              metrics={"fleet.routed": 3})
        te = obj["traceEvents"]
        meta = [e for e in te if e["ph"] == "M"]
        assert {"process_name", "thread_name"} <= {e["name"] for e in meta}
        xs = [e for e in te if e["ph"] == "X"]
        instants = [e for e in te if e["ph"] == "i"]
        assert len(xs) == 2 and len(instants) == 1
        assert instants[0]["s"] == "t"
        # ts/dur on the wire are microseconds
        chunk = next(e for e in xs if e["name"] == "chunk")
        assert chunk["ts"] == pytest.approx(0.5e6)
        assert chunk["dur"] == pytest.approx(0.25e6)
        assert obj["otherData"]["metrics"] == {"fleet.routed": 3}
        assert obj["otherData"]["dropped_spans"] == 0
        json.dumps(obj)                      # serializable as-is

    def test_round_trip_restores_seconds_and_tracks(self):
        tr = self._tracer()
        text = json.dumps(to_chrome_trace(tr))
        back = from_chrome_trace(text)
        spans = {s["name"]: s for s in back["spans"]}
        assert spans["chunk"]["track"] == "replica:0"
        assert spans["chunk"]["t0"] == pytest.approx(0.5)
        assert spans["chunk"]["dur"] == pytest.approx(0.25)
        assert spans["step"]["args"]["step"] == 3
        (ev,) = back["events"]
        assert (ev["name"], ev["track"], ev["t0"]) \
            == ("fail", "replica:0", pytest.approx(2.0))
        assert ev["args"]["block"] == 4
        assert sorted(back["tracks"].values()) \
            == ["replica:0", "train"]

    def test_telemetry_write_trace(self, tmp_path):
        obs = Telemetry(tracing=True, clock=VirtualClock())
        obs.metrics.counter("n").inc()
        with obs.span("w", track="t"):
            pass
        path = tmp_path / "trace.json"
        obs.write_trace(str(path))
        back = from_chrome_trace(str(path))
        assert [s["name"] for s in back["spans"]] == ["w"]
        assert back["otherData"]["metrics"]["n"] == 1
