"""The reduction from a profiler trace to busy time, programs, operations and
labelled idle gaps: on a synthetic trace whose answers are known, and on a
small trace recorded on a TPU v5e."""
import time
from pathlib import Path

import pytest

from bench import trace as TR

FIXTURE = Path(__file__).parent / "data" / "v5e_decode_kernel.xplane.pb"


def E(name, start, end):
    return TR.Event(name, start, end)


@pytest.fixture
def synthetic():
    # chip 0: two programs with a 5 us gap inside the first and a 2 ms gap
    # between them, while the host was sampling; chip 1 idle after 6 ms
    ops = {0: [E("fusion.1", 1.000, 1.002), E("fusion.2", 1.002005, 1.004),
               E("custom-call.3", 1.006, 1.009)],
           1: [E("fusion.1", 1.000, 1.006)]}
    modules = {0: [E("jit__admit(11)", 1.000, 1.004),
                   E("jit__decode(12)", 1.006, 1.009)],
               1: [E("jit__admit(11)", 1.000, 1.006),
                   E("jit__late(13)", 1.0095, 1.011)]}
    host = [E(TR.WINDOW, 1.000, 1.010), E("bench.step_chunk", 1.000, 1.005),
            E("bench.sample", 1.004, 1.0062)]
    return TR.Trace(ops, modules, host)


def test_busy_and_idle(synthetic):
    s = TR.reduce(synthetic)
    assert s.window_s == pytest.approx(0.010)
    assert s.chips == 2
    # chip 0 busy 2 + 1.995 + 3 ms, chip 1 busy 6 ms
    assert s.busy_s == pytest.approx((0.006995 + 0.006) / 2)
    assert s.idle_share == pytest.approx(1 - s.busy_s / 0.010)


def test_programs_counted_whole_and_by_name(synthetic):
    s = TR.reduce(synthetic)
    assert sorted(s.program_s("_admit")) == pytest.approx([0.004, 0.006])
    assert s.program_s("_decode") == pytest.approx([0.003])
    assert s.program_s("_late") == []          # runs past the window


def test_ops_and_gap_labels(synthetic):
    s = TR.reduce(synthetic)
    assert s.ops_s["fusion.1"] == pytest.approx((0.002 + 0.006) / 2)
    assert s.op_events["custom-call.3"] == pytest.approx([0.003])
    assert s.idle_gaps[TR.SHORT_GAP] == 1
    # the 2 ms gap of chip 0 is labelled by the innermost annotation at its
    # middle; chip 0's last 1 ms and chip 1's last 4 ms lie outside them
    assert s.idle_s["bench.sample"] == pytest.approx(0.002 / 2)
    assert s.idle_gaps["outside annotations"] == 2
    b = s.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_self_time_of_nested_ops():
    ops = [E("%while.1 = (s32[]) while(x)", 0.0, 10.0),
           E("%fusion.2 = f32[8]{0} fusion(y)", 1.0, 3.0),
           E("%custom-call.3 = bf16[4,8]{1,0:T(8,128)} custom-call(z)",
             4.0, 9.0),
           E("%copy.4 = f32[8]{0} copy(w)", 5.0, 6.0),
           E("%add.5 = f32[8]{0} add(v)", 11.0, 12.0)]
    assert TR.self_times(ops) == pytest.approx([3.0, 2.0, 4.0, 1.0, 1.0])
    assert TR.short_name(ops[2].name) == \
        "%custom-call.3 custom-call bf16[4,8]"
    assert TR.short_name(ops[0].name) == "%while.1 while (s32[])"


def test_union_and_clip():
    assert TR.union([E("a", 0, 2), E("b", 1, 3), E("c", 4, 5)]) == \
        [(0, 3), (4, 5)]
    assert TR.clip([E("a", 0, 2), E("b", 3, 4)], 1, 3.5) == \
        [E("a", 1, 2), E("b", 3, 3.5)]
    assert TR.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_recorded_v5e_trace():
    """Three runs of a jitted program around the Pallas decode-attention
    kernel, each in a ``bench.step`` annotation, then a 2 ms host sleep.
    The device's clock in this trace runs about 1 ms ahead of the host's,
    so the first run's operations precede the traced window's annotation:
    the whole trace is reduced here."""
    tr = TR.load(str(FIXTURE))
    assert sorted(tr.ops) == [0]
    lo, hi = TR.window_of(tr)
    assert sum(lo <= e.start for e in tr.ops[0]) == 8
    events = tr.ops[0] + tr.modules[0] + tr.host
    s = TR.reduce(tr, min(e.start for e in events),
                  max(e.end for e in events))
    assert 0 < s.busy_s < s.window_s
    kernel = [n for n in s.op_events if "tpu_custom_call" in n]
    assert kernel and len(s.op_events[kernel[0]]) == 3
    runs = [d for n, ds in s.programs.items() for d in ds]
    assert len(runs) == 3 and all(0 < d < s.window_s for d in runs)
    assert s.idle_s.get("bench.host_wait", 0) > 0.004


def test_serve_readers_on_a_synthetic_trace():
    """Each serving reader finds its program or kernel by name and turns the
    traced chunks' cached lengths into its share."""
    from types import SimpleNamespace

    from bench import counting
    from bench import harness as H
    from bench.peaks import PEAKS

    cell = H.find_cell(H.load_benchmark(), "olmo1b-serve-chat")
    kernel = ("%closed_call.9 = bf16[12,16,128]{2,1,0:T(8,128)(2,1)} "
              "custom-call(s32[12]{0} %a), "
              'custom_call_target="tpu_custom_call"')
    ops = {0: [E("%while.1 = (s32[]) while(x)", 0.0, 0.3),
               E(kernel, 0.1, 0.1002), E(kernel, 0.2, 0.2002),
               E("%fusion.2 = bf16[12,1024,2048]{2,1,0} fusion(y)",
                 0.35, 0.5)]}
    modules = {0: [E("jit__decode(1)", 0.0, 0.3),
                   E("jit__admit(2)", 0.35, 0.5)]}
    tr = TR.Trace(ops, modules, [E(TR.WINDOW, 0.0, 0.6)])
    s = TR.reduce(tr)
    # two chunks of one step each: three live requests, then two
    out = SimpleNamespace(records={"decode_chunks": [
        [[1025], [1100], [1500]], [[1026], [1101], []]]})
    job = SimpleNamespace(cell=cell, peaks=PEAKS["TPU v5 lite"])
    read = {m: H.metric_reader(m).read(s, job, out) for m in (
        "serve.prefill_share", "serve.decode_chunk_ms", "serve.decode_mfu",
        "decode_attention_roofline", "device.idle_share.serve")}
    assert read["serve.prefill_share"] == pytest.approx(100 * 0.15 / 0.45)
    assert read["serve.decode_chunk_ms"] == pytest.approx(300.0)
    assert read["device.idle_share.serve"] == pytest.approx(25.0)
    c = cell.config
    pk = PEAKS["TPU v5 lite"]
    byte_s = sum(counting.decode_attention_bytes(c, lens)
                 for lens in ([1025, 1100, 1500], [1026, 1101]))
    assert read["decode_attention_roofline"] == pytest.approx(
        100 * c["num_hidden_layers"] * byte_s / pk["hbm_bytes_per_s"]
        / 0.0004)
    flops = (counting.decode_flops(c, [1025, 1100, 1500])
             + counting.decode_flops(c, [1026, 1101]))
    assert read["serve.decode_mfu"] == pytest.approx(
        100 * flops / (0.3 * pk["bf16_flops"]))


def test_ttft_reader_takes_the_windows_tail_or_nothing():
    from types import SimpleNamespace

    from bench import harness as H
    reader = H.metric_reader("serve.ttft_p90_ms")
    out = SimpleNamespace(metrics={"ttft_p90_ms": 1234.5, "setup_s": 20.0})
    assert reader.read(None, None, out) == 1234.5
    assert reader.read(None, None, SimpleNamespace(metrics={})) is None


def test_train_reader_on_a_synthetic_trace():
    from types import SimpleNamespace

    from bench import counting
    from bench import harness as H
    from bench.peaks import PEAKS

    cell = H.find_cell(H.load_benchmark(), "olmo1b-train-2k")
    modules = {0: [E("jit_train_step(5)", 0.0, 1.2),
                   E("jit_train_step(5)", 1.2, 2.4)]}
    tr = TR.Trace({0: [E("%fusion.1 = f32[8]{0} fusion(x)", 0.0, 2.4)]},
                  modules, [E(TR.WINDOW, 0.0, 2.5)])
    job = SimpleNamespace(cell=cell, peaks=PEAKS["TPU v5 lite"])
    got = H.metric_reader("train.mfu").read(TR.reduce(tr), job, None)
    flops = counting.train_flops(cell.config, 16, 2048)
    assert got == pytest.approx(100 * 2 * flops / (2.5 * 197e12))
    assert 0 < got < 100


class _SlowProfiler:
    """Starts and stops as the profiler does, each taking ``hold`` s."""
    on = True

    def __init__(self, hold):
        self.hold, self.active, self.done = hold, False, False

    def start(self):
        if not (self.active or self.done):
            time.sleep(self.hold)
            self.active = True

    def stop(self):
        if self.active:
            time.sleep(self.hold)
            self.active, self.done = False, True


def test_ttft_leaves_out_requests_the_profiler_held():
    import bench_tiny as tiny
    out = tiny.run(tiny.serve_cell(), seconds=2.0,
                   tracer=_SlowProfiler(0.5))
    held = out.info["profiler_held_s"]
    assert len(held) == 2 and min(held) >= 0.5
    # every request served after the stop began is left out
    assert out.info["ttft_held_by_profiler"] >= 1
    assert out.metrics["ttft_p90_ms"] < 500
    plain = tiny.run(tiny.serve_cell(), seconds=2.0)
    assert plain.info["profiler_held_s"] == []
    assert plain.info["ttft_held_by_profiler"] == 0
