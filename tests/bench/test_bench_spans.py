"""The program's spans read from a profiler trace: the interval arithmetic
that gives device idle time to the innermost span, the readers on
hand-built spans, and a small pooled engine profiled on the CPU."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness as H
from bench import spans as SP
from bench import trace as TR


def S(name, start, end, **args):
    return SP.Span(name, start, end, args)


def test_idle_split_exactly_between_two_spans():
    spans = [S("serve.admit.plan", 1.0, 2.0), S("serve.decode", 2.0, 4.0)]
    # 1.5-3.0 idle: half a second under each
    assert SP.idle_by_span([(1.5, 3.0)], spans) == pytest.approx(
        {"serve.admit.plan": 0.5, "serve.decode": 1.0})


def test_idle_goes_to_the_innermost_span():
    spans = [S("serve.step_chunk", 0.0, 10.0), S("serve.decode", 2.0, 8.0),
             S("serve.decode.sync", 3.0, 5.0)]
    got = SP.idle_by_span([(1.0, 4.0), (9.0, 11.0)], spans)
    assert got == pytest.approx({"serve.step_chunk": 2.0,
                                 "serve.decode": 1.0,
                                 "serve.decode.sync": 1.0})


def _spans(spans, idle, window=(0.0, 10.0)):
    sp = SP.Spans("unused", window, spans, [])
    sp.idle = idle                    # in place of the device's trace
    return sp


def test_stall_leaves_out_sync_and_time_outside_the_spans(monkeypatch):
    spans = [S("serve.decode", 1.0, 5.0, live=2, steps=8),
             S("serve.decode.dispatch", 1.0, 2.0),
             S("serve.decode.sync", 2.0, 4.0),
             S("serve.decode.bookkeeping", 4.0, 5.0),
             S("serve.decode", 6.0, 9.0, live=2, steps=8),
             S("serve.decode.sync", 6.5, 8.5)]
    # chip 0: idle in the dispatch (0.5 s), the sync (1 s), the bookkeeping
    # (0.25 s), the second chunk's own body (0.5 s) and under no span (1 s);
    # chip 1: idle in the second chunk's body only (0.5 s)
    sp = _spans(spans, {0: [(1.5, 3.0), (4.75, 5.5), (6.0, 6.5), (9.0, 9.5)],
                        1: [(8.5, 9.0)]})
    stall = (0.5 + 0.25 + 0.5 + 0.5) / 2
    assert SP.stall_s(sp, "serve.", lambda n: n.endswith(".sync")) == \
        pytest.approx(stall)
    # the serving reader counts the waits too, per decode chunk
    monkeypatch.setattr(SP, "for_job", lambda job: sp)
    got = H.metric_reader("serve.host_stall_ms").read(None, None, None)
    assert got == pytest.approx(1e3 * (stall + 1.0 / 2) / 2)


def test_spans_outside_the_window_are_ignored():
    window = (10.0, 20.0)
    spans = [S("train.step", 5.0, 9.0), S("train.batch", 5.0, 6.0),
             S("train.step", 9.5, 12.0), S("train.batch", 9.5, 10.5),
             S("train.log", 11.0, 12.0), S("train.step", 12.0, 21.0),
             S("train.dispatch", 12.0, 13.0)]
    # the window's idle time: the first step lies before the window; of the
    # second only its part from 10.0 counts, and its log is a wait
    sp = _spans(spans, {0: [(10.0, 10.25), (11.0, 11.5), (12.5, 13.5),
                            (19.0, 20.0)]}, window)
    assert [s.start for s in sp.named("train.step")] == [12.0]
    stall = SP.stall_s(sp, "train.", lambda n: n == "train.log")
    assert stall == pytest.approx(0.25 + 0.5 + 0.5 + 1.0)


def test_readers_return_nothing_without_spans():
    job = SimpleNamespace(tracer=SimpleNamespace(dir=None))
    for name in ("serve.prefill_token_use", "serve.host_stall_ms",
                 "serve.compiles_in_window", "train.host_stall_ms"):
        assert H.metric_reader(name).read(None, job, None) is None
    assert SP.stall_s(_spans([], {}), "serve.", bool) is None
    assert SP.idle_by_span([(0.0, 1.0)], []) == {}


LENS, BUDGETS = [5, 20, 9], [2, 6, 2]


def _engine(**spec):
    import bench_tiny as tiny
    from bench import program as PROG
    from bench.reference import olmo as REF
    from repro.serve.engine import ServeEngine

    spec = PROG.SliceSpec(**{**dict(slots=2, max_len=32, prompt_len=24,
                                    chunk=4, kv_block=8, kv_share=False,
                                    suffix_len=8), **spec})
    return ServeEngine(PROG.model_config(tiny.TINY),
                       REF.init_weights(tiny.TINY, 11), spec)


def _serve_traced(eng, rng):
    """Serve ``LENS`` to the end with the traced window open around it,
    as the benchmark's tracer does."""
    tracer = H.Tracer(True)
    tracer.start()
    reqs = [eng.submit(rng.integers(0, eng.cfg.vocab_size, n),
                       max_new_tokens=b) for n, b in zip(LENS, BUDGETS)]
    while eng.depth:
        eng.step_chunk()
    tracer.stop()
    return SimpleNamespace(job=SimpleNamespace(tracer=tracer), reqs=reqs,
                           spec=eng.spec)


@pytest.fixture(scope="module")
def pooled_profile():
    """A tiny pooled engine, warmed up, then profiled."""
    eng = _engine()
    rng = np.random.default_rng(3)
    for n in LENS:
        eng.submit(rng.integers(0, eng.cfg.vocab_size, n), max_new_tokens=2)
    while eng.depth:
        eng.step_chunk()
    run = _serve_traced(eng, rng)
    yield run
    run.job.tracer.cleanup()


def test_prefill_token_use_counts_the_admitted_prompts(pooled_profile):
    """Two slots, suffix 8: the first wave seats the 5- and 20-token
    prompts in ceil(20 / 8) = 3 dispatches of 2 x 8 rows (5 + 8, 8, 4
    tokens); the 9-token prompt follows alone, once the first request is
    done, in 2 (8, 1)."""
    p = pooled_profile
    assert all(r.done for r in p.reqs)
    sp = SP.for_job(p.job)
    runs = sp.named("serve.admit.prefill")
    assert [s.args["tokens"] for s in runs] == [13, 8, 4, 8, 1]
    assert {s.args["width"] for s in runs} == {2 * 8}
    assert [s.args["requests"] for s in sp.named("serve.admit")] == [2, 1]
    got = H.metric_reader("serve.prefill_token_use").read(None, p.job, None)
    assert got == pytest.approx(100 * sum(LENS) / (5 * 2 * 8))


def test_engine_spans_nest_and_compile_nothing(pooled_profile):
    sp = SP.for_job(pooled_profile.job)
    chunks = sp.named("serve.step_chunk")
    decodes = sp.named("serve.decode")
    assert chunks and decodes
    for d in decodes:
        assert any(c.start <= d.start and d.end <= c.end for c in chunks)
        assert d.args["steps"] == pooled_profile.spec.chunk
        kids = [s.name for s in sp.spans
                if d.start <= s.start and s.end <= d.end and s is not d]
        assert kids == ["serve.decode.dispatch", "serve.decode.sync",
                        "serve.decode.bookkeeping"]
    assert chunks[0].args == {"live": 0, "pending": 3}
    # warmed up before the window: nothing lowered while serving
    assert H.metric_reader("serve.compiles_in_window").read(
        None, pooled_profile.job, None) == 0
    # the CPU has no device planes, so no idle time to give out
    assert TR.load(TR.find_xplane(pooled_profile.job.tracer.dir)).ops == {}
    assert H.metric_reader("serve.host_stall_ms").read(
        None, pooled_profile.job, None) is None


def test_a_program_compiled_while_serving_is_counted():
    """An engine of a shape not served before lowers its admission and
    decode programs inside the window's first chunk."""
    run = _serve_traced(_engine(chunk=3), np.random.default_rng(4))
    try:
        got = H.metric_reader("serve.compiles_in_window").read(
            None, run.job, None)
    finally:
        run.job.tracer.cleanup()
    assert got >= 2
