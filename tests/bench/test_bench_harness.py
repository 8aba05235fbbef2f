"""The benchmark's harness: traffic from the seed, cells found by name, and
no run without a chip whose peaks are known."""
import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import generator as GEN
from bench import harness as H
from bench.peaks import UnknownDevice, peaks_for

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return H.load_benchmark()


@pytest.fixture(scope="module")
def chat():
    return H.find_cell(H.load_benchmark(), "olmo1b-serve-chat").mix


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def test_same_seed_same_requests(chat):
    a = GEN.serve_requests(chat, 2**33 + 1, 51, 50304)
    b = GEN.serve_requests(chat, 2**33 + 1, 51, 50304)
    assert np.array_equal(a.t_arrival, b.t_arrival)
    assert np.array_equal(a.output_len, b.output_len)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


@pytest.fixture(scope="module")
def blocked(chat):
    """The chat mix shuffled within blocks of 8, as a mix may ask."""
    return {**chat, "shuffle_block": 8}


def test_other_seed_same_work_in_another_order(blocked):
    a = GEN.serve_requests(blocked, 11, 51, 50304)
    b = GEN.serve_requests(blocked, 2**40 + 3, 51, 50304)
    assert not np.array_equal(a.output_len, b.output_len)
    assert sorted(a.output_len) == sorted(b.output_len)
    assert sorted(map(len, a.prompts)) == sorted(map(len, b.prompts))
    assert np.isclose(a.t_arrival[-1], b.t_arrival[-1])
    assert a.t_arrival[-1] > 51          # every seed outlasts the window


def test_chat_offers_one_schedule_to_every_seed(chat):
    a = GEN.serve_requests(chat, 13, 51, 50304)
    b = GEN.serve_requests(chat, 2**42 + 5, 51, 50304)
    assert np.array_equal(a.t_arrival, b.t_arrival)
    assert np.array_equal(a.output_len, b.output_len)
    assert [len(p) for p in a.prompts] == [len(p) for p in b.prompts]
    assert not all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


def test_each_block_offers_the_same_work(blocked):
    block = blocked["shuffle_block"]
    a = GEN.serve_requests(blocked, 12, 51, 50304)
    b = GEN.serve_requests(blocked, 2**41 + 7, 51, 50304)
    ends = np.arange(block - 1, len(a), block)
    assert np.allclose(a.t_arrival[ends], b.t_arrival[ends])
    for s in range(0, len(a), block):
        got = [sorted(zip(map(len, r.prompts[s:s + block]),
                          r.output_len[s:s + block])) for r in (a, b)]
        assert got[0] == got[1]
    assert not np.array_equal(a.t_arrival, b.t_arrival)


def test_lengths_and_rate_as_declared(chat):
    r = GEN.serve_requests(chat, 3, 3000, 50304)
    plen = np.array([len(p) for p in r.prompts])
    for got, spec in ((plen, chat["prompt_len"]),
                      (r.output_len, chat["output_len"])):
        assert got.min() >= spec["min"] and got.max() <= spec["max"]
        assert abs(np.median(got) / spec["median"] - 1) < 0.05
        # the lognormal's tail above the clip, P(z > ln(max/median)/sigma)
        z = np.log(spec["max"] / spec["median"]) / spec["sigma"]
        tail = 0.5 * math.erfc(z / math.sqrt(2))
        assert abs(np.mean(got == spec["max"]) - tail) < 0.03
    gaps = np.diff(r.t_arrival)
    assert abs(1 / np.mean(gaps) / chat["rate_per_s"] - 1) < 0.05
    assert all(0 <= p.min() and p.max() < 50304 for p in r.prompts)


def test_lm_batch_is_the_programs_dataset():
    from repro.configs import registry
    from repro.configs.base import ShapeConfig
    from repro.data.synthetic import Dataset
    cfg = registry.get_reduced("olmo-1b")
    seed = 2**35 + 9
    ds = Dataset(cfg, ShapeConfig("t", "train", 16, 3), seed=seed)
    for step in (0, 2):
        tokens, labels = GEN.lm_batch(seed, step, 3, 16, cfg.vocab_size)
        want = ds.batch(step)
        assert np.array_equal(tokens, want["tokens"])
        assert np.array_equal(labels, want["labels"])


# ---------------------------------------------------------------------------
# cells, mixes and metrics found by name
# ---------------------------------------------------------------------------

def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = H.find_cell(bench, w["name"])
        H.driver(cell.mix["kind"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert callable(H.metric_reader(m["name"]).read)


def test_a_new_cell_needs_only_new_files(tmp_path, bench):
    """A later cell adds a configuration, a mix, its limits and a metric as
    files of their own, and entries in BENCHMARK.json: nothing that is
    there changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    new = json.loads(json.dumps(bench))
    cfg = json.load(open(ROOT / "bench/configs/olmo-1b.json"))
    cfg["num_hidden_layers"] = 8
    (tmp_path / "bench/configs/olmo-1b-8l.json").write_text(json.dumps(cfg))
    mix = json.load(open(ROOT / "bench/traffic/chat.json"))
    mix["rate_per_s"] = 3.0
    (tmp_path / "bench/traffic/chat-fast.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/olmo1b8-serve-fast.json").write_text(
        json.dumps({"sample_requests": 4, "max_logit_gap": 1.0}))
    (tmp_path / "bench/metrics/serve.new_metric.py").write_text(
        "def read(summary, job, out):\n    return 1.0\n")
    new["configs"].append({"name": "olmo-1b-8l", "source": "x",
                           "file": "bench/configs/olmo-1b-8l.json",
                           "reduced": ["num_hidden_layers"], "why": "x"})
    new["workloads"].append({"name": "olmo1b8-serve-fast",
                             "config": "olmo-1b-8l", "traffic": "chat-fast",
                             "chips": 1, "why": "x"})
    for m in new["end_to_end"]:
        if "olmo1b-serve-chat" in m.get("workloads", []):
            m["workloads"].append("olmo1b8-serve-fast")
    new["per_layer"].append({"name": "serve.new_metric", "unit": "%",
                             "better": "higher", "source": "device_trace",
                             "layer": "serve engine", "moves": "tpot_p90_ms",
                             "workloads": ["olmo1b8-serve-fast"]})
    cell = H.find_cell(new, "olmo1b8-serve-fast", root=tmp_path)
    assert cell.config["num_hidden_layers"] == 8
    assert cell.mix["rate_per_s"] == 3.0
    assert cell.limits["sample_requests"] == 4
    assert "serve.new_metric" in {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "tpot_p90_ms", "setup_s"}
    assert H.metric_reader("serve.new_metric", root=tmp_path).read(
        None, None, None) == 1.0
    for rel in ("bench/configs/olmo-1b.json", "bench/traffic/chat.json",
                "bench/harness.py"):
        assert (tmp_path / rel).read_bytes() == (ROOT / rel).read_bytes()


def test_benchmark_json_keeps_the_contracts_shapes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    assert 1 <= bench["run_seconds"] <= 51


def test_configs_are_the_programs_olmo_1b():
    from bench import program as PROG
    from repro.configs import registry
    olmo = registry.get_config("olmo-1b")
    serve = json.load(open(ROOT / "bench/configs/olmo-1b.json"))
    train = json.load(open(ROOT / "bench/configs/olmo-1b-4l.json"))
    assert PROG.model_config(serve) == olmo
    assert PROG.model_config(train) == olmo.replace(num_layers=4)
    changed = {k for k in serve if serve[k] != train.get(k)} - {
        "reduced", "published", "deployment"}
    assert changed == set(train["reduced"]) == {"num_hidden_layers"}


# ---------------------------------------------------------------------------
# no run without a chip
# ---------------------------------------------------------------------------

def test_cpu_is_refused():
    with pytest.raises(H.NoChip):
        H.require_chips(1)


def test_unknown_device_kind_is_refused():
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v99 imaginary")
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo1b-serve-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_on_a_cpu_exits_without_a_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_run_without_the_program_exits_without_a_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
