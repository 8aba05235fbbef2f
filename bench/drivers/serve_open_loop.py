"""Open-loop serving: requests arrive on their own clock through
``Supercomputer().allocate`` -> ``Slice.serve`` -> ``ServeSession.submit``
/ ``step_chunk``, and the window's tails are read on the host's monotonic
clock.

Set-up makes the weights on the device from the seed, opens the session and
warms up the two programs the window drives (admission at the mix's full
slot width, and one decode chunk).  In the window each request is submitted
once its scheduled time has passed; between submissions the loop advances
the engine one chunk at a time.  A request's time to first token runs from
its scheduled arrival to the return of the ``step_chunk`` that first hands
back one of its tokens; its time per output token is the time from then to
the return that completes it, over its tokens after the first.

Afterwards the session is closed and a sample of the completed requests
drawn from the seed, the longest among them, goes through the float32
reference, given each request's own prompt from position 0: each served
token's logit must lie within the limit of the reference's best at its
position.  With ``--control`` the float8 reference's choice at each
position is judged in the program's place, so the run is not correct.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import generator as GEN
from bench import harness as H
from bench import program as PROG


def _percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def _p50(of: dict, keys) -> float:
    return _percentile([of[k] for k in keys], 50) if keys else None


def _warm_up(session, spec, vocab: int, seed: int) -> None:
    """Compile and run once each program the window drives: admissions of
    full-length prompts into every slot and one decode chunk, twice over."""
    rng = np.random.default_rng([seed % 2**63, 3])
    for _ in range(2):
        reqs = [session.submit(rng.integers(0, vocab, spec.prompt_len),
                               max_new_tokens=spec.chunk + 1)
                for _ in range(spec.slots)]
        while not all(r.done for r in reqs):
            session.step_chunk()


def run(job: H.Job) -> H.Outcome:
    c, mix, REF = job.cell.config, job.cell.mix, job.cell.reference
    cfg = PROG.model_config(c)
    spec = PROG.SliceSpec(**mix["engine"])
    n_out = GEN.longest(mix["output_len"])
    width = spec.prompt_len - 1 + n_out
    reqs = GEN.serve_requests(mix, job.seed, job.seconds, c["vocab_size"])
    # the engine would keep only a prompt's last ``prompt_len`` tokens
    assert GEN.longest(mix["prompt_len"]) <= spec.prompt_len
    weights = REF.init_weights(c, job.seed)
    trace_from = min(mix["trace_from_s"], job.seconds / 4)
    trace_to = trace_from + min(mix["trace_s"], job.seconds / 2)

    phases = {"start_s": time.perf_counter() - job.t_start}
    with PROG.Supercomputer().allocate(PROG.SLICE) as sl:
        session = sl.serve(cfg, jax.block_until_ready(weights), spec)
        phases["weights_s"] = time.perf_counter() - job.t_start
        _warm_up(session, spec, c["vocab_size"], job.seed)

        handles, t_first, t_done = {}, {}, {}
        before = {}
        chunks = []                   # decode work of each traced chunk
        late = []
        held = []                     # spans the profiler's start/stop held the loop
        t0 = time.perf_counter()
        setup_s = t0 - job.t_start
        i, now = 0, 0.0
        while now < job.seconds:
            was = job.tracer.active
            if trace_from <= now < trace_to:
                job.tracer.start()
            elif now >= trace_to:
                job.tracer.stop()
            if job.tracer.active != was:
                held.append((now, time.perf_counter() - t0))
            with H.span("submit"):
                while i < len(reqs) and reqs.t_arrival[i] <= now:
                    handles[i] = session.submit(
                        reqs.prompts[i],
                        max_new_tokens=int(reqs.output_len[i]))
                    late.append(now - reqs.t_arrival[i])
                    i += 1
            if session.depth == 0:
                nxt = reqs.t_arrival[i] if i < len(reqs) else job.seconds
                with H.span("wait_arrival"):
                    time.sleep(max(0.0, min(nxt, job.seconds) - now))
                now = time.perf_counter() - t0
                continue
            live = [k for k in handles if k not in t_done]
            traced = job.tracer.active
            if traced:
                before = {k: len(handles[k].out_tokens) for k in live}
            with H.span("step_chunk"):
                session.step_chunk()
            now = time.perf_counter() - t0
            with H.span("bookkeeping"):
                for k in live:
                    r = handles[k]
                    if r.out_tokens and k not in t_first:
                        t_first[k] = now
                    if r.done:
                        t_done[k] = now
                if traced and job.tracer.active:
                    chunks.append([
                        [len(reqs.prompts[k]) + max(before[k], 1) + s
                         for s in range(len(handles[k].out_tokens)
                                        - max(before[k], 1))]
                        for k in live])
        window_s = now
        job.tracer.stop()
        mem = H.memory_peak_bytes()
        pending = session.depth
        done = sorted(t_done)
        served = {k: np.asarray(handles[k].out_tokens, np.int32)
                  for k in done}
        session.engine.cache = None
        session.close()
        del session

    failed = [k for k in done
              if len(served[k]) != reqs.output_len[k]
              or not np.all((0 <= served[k]) & (served[k] < c["vocab_size"]))]
    # every request that saw its first token in the window, finished or not;
    # in a traced run only those served before the profiler's stop (which
    # holds the loop for seconds and leaves a backlog) and not waiting
    # while it started
    stop_at = held[1][0] if len(held) > 1 else np.inf
    ttft_of = {k: (t_first[k] - reqs.t_arrival[k]) * 1e3 for k in t_first
               if t_first[k] <= stop_at
               and not any(a < t_first[k] and reqs.t_arrival[k] < b
                           for a, b in held)}
    ttft = list(ttft_of.values())
    tpot = [(t_done[k] - t_first[k]) * 1e3 / (len(served[k]) - 1)
            for k in done if len(served[k]) > 1 and t_done[k] > t_first[k]]
    metrics = {"setup_s": setup_s}
    if ttft:
        metrics["ttft_p90_ms"] = _percentile(ttft, 90)
    if tpot:
        metrics["tpot_p90_ms"] = _percentile(tpot, 90)

    checks, sample, readings = _check(job, c, reqs, served, done, width,
                                      n_out, weights)
    first = [k for k in ttft_of if k < i / 2]
    second = [k for k in ttft_of if k >= i / 2]
    info = {"cell": job.cell.name, "seed": job.seed, "window_s": window_s,
            "submitted": i, "completed": len(done),
            "in_engine_at_close": pending,
            "waiting_at_close": sum(1 for k in handles if k not in t_first),
            "offered_rate_per_s": mix["rate_per_s"],
            "ttft_p50_ms_first_half": _p50(ttft_of, first),
            "ttft_p50_ms_second_half": _p50(ttft_of, second),
            "ttft_p50_ms": _percentile(ttft, 50) if ttft else None,
            "ttft_p90_ms": metrics.get("ttft_p90_ms"),
            "ttft_held_by_profiler": len(t_first) - len(ttft_of),
            "profiler_held_s": [b - a for a, b in held],
            "tpot_p50_ms": _percentile(tpot, 50) if tpot else None,
            "tpot_samples": len(tpot),
            "generator_late_ms_mean": 1e3 * float(np.mean(late)) if late
            else None,
            "generator_late_ms_max": 1e3 * float(np.max(late)) if late
            else None,
            "checked_requests": len(sample),
            "checked_tokens": int(sum(len(served[k]) for k in sample)),
            "setup_phases": phases,
            **readings}
    return H.Outcome(metrics=metrics, attempted=i, failed=len(failed),
                     checks=checks, memory_peak_bytes=mem,
                     records={"decode_chunks": chunks}, info=info)


def _check(job, c, reqs, served, done, width, n_out, weights):
    """The widest gap, over a sample of completed requests, by which a
    served token's logit lies below the reference's best at its position.
    The sample is drawn from the seed and always holds the longest
    request.  With the control, the gap of the float8 reference's own
    choice stands in the program's place."""
    lim, REF = job.cell.limits, job.cell.reference
    if not done:
        return {"completed_requests": {"value": 1.0, "limit": 0.0}}, [], {}
    rng = np.random.default_rng([job.seed % 2**63, 4])
    longest = max(done, key=lambda k: len(served[k]))
    rest = [k for k in done if k != longest]
    n = min(len(rest), lim["sample_requests"] - 1)
    sample = [longest] + [rest[j] for j in
                          sorted(rng.choice(len(rest), n, replace=False))]
    worst = ctrl = 0.0
    readings = {}
    for k in sample:
        gaps, cgaps = REF.served_gaps(c, weights, reqs.prompts[k], served[k],
                                      width, n_out, control=job.control)
        worst = max(worst, float(gaps.max()))
        ctrl = max(ctrl, float(cgaps.max()))
    if job.control:
        # a fault planted where tokens are produced: one token of the
        # longest request replaced by another drawn from the seed
        readings["program_max_logit_gap"] = worst
        k = sample[0]
        bad = served[k].copy()
        j = len(bad) // 2
        bad[j] = (bad[j] + 1 + rng.integers(c["vocab_size"] - 1)) \
            % c["vocab_size"]
        gaps, _ = REF.served_gaps(c, weights, reqs.prompts[k], bad, width,
                                  n_out)
        readings["fault_altered_token_max_logit_gap"] = float(gaps.max())
        worst = ctrl
    checks = {"max_logit_gap": {"value": worst,
                                "limit": lim["max_logit_gap"]}}
    return checks, sample, readings
