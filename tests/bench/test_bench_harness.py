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
from bench import program as PROG
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
    cfg["program"]["replace"]["num_layers"] = 8
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
    assert PROG.model_config(cell.config).num_layers == 8
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


def _bench_files(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


# a reference in the interface's shape whose functions do nothing: the
# harness finds it, and no run calls it
STUB_REFERENCE = """\"\"\"A stand-in reference of a mixture-of-experts decoder.\"\"\"
NAME = "qwen3_moe stub"


def init_weights(c, seed):
    return {}


def flatten(tree):
    return dict(tree)


def served_gaps(c, weights, prompt, served, width, n_out, control=False):
    return None, None


def train_steps(c, opt, weights, batches, quant=None):
    return [], {}, {}
"""

# qwen3-moe-30b-a3b from the program's registry, cut to widths a test holds
MOE_REPLACE = {"num_layers": 2, "d_model": 64, "d_ff": 32, "vocab_size": 512,
               "max_seq_len": 128, "attention.num_heads": 4,
               "attention.num_kv_heads": 2, "attention.head_dim": 16,
               "moe.num_experts": 8, "moe.top_k": 2, "moe.expert_ffw": 32}


def _add_config(root: Path, bench: dict, name: str, config: dict) -> dict:
    """``bench`` with a configuration ``name`` of file ``config`` and a
    serving cell ``<name>-serve`` on a mix and limits of its own, all
    written under ``root`` as new files."""
    (root / f"bench/configs/{name}.json").write_text(json.dumps(config))
    mix = json.load(open(ROOT / "bench/traffic/chat.json"))
    (root / f"bench/traffic/{name}-chat.json").write_text(json.dumps(mix))
    (root / f"bench/limits/{name}-serve.json").write_text(
        json.dumps({"sample_requests": 4, "max_logit_gap": 0.5}))
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": name, "source": "x",
                           "file": f"bench/configs/{name}.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": f"{name}-serve", "config": name,
                             "traffic": f"{name}-chat", "chips": 1,
                             "why": "x"})
    for m in new["end_to_end"]:
        if "olmo1b-serve-chat" in m.get("workloads", []):
            m["workloads"].append(f"{name}-serve")
    return new


def test_a_configuration_of_another_architecture_needs_only_new_files(
        tmp_path, bench):
    """A configuration of an architecture the program's registry has is
    added as files of its own (its configuration with a ``program`` block,
    its reference, a mix and limits) and entries in BENCHMARK.json: the
    cell finds that reference, the program's configuration is that family
    at those widths, and no file that is there changes."""
    from repro.configs.base import AttentionConfig, MoEConfig
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _bench_files(ROOT)
    (tmp_path / "bench/reference/qwen3_moe.py").write_text(STUB_REFERENCE)
    config = {"model": "qwen3-moe-tiny", "architecture": "qwen3_moe",
              "program": {"config": "qwen3-moe-30b-a3b",
                          "replace": MOE_REPLACE},
              "vocab_size": 512, "param_dtype": "float32",
              "compute_dtype": "bfloat16"}
    new = _add_config(tmp_path, bench, "qwen3-moe-tiny", config)
    cell = H.find_cell(new, "qwen3-moe-tiny-serve", root=tmp_path)
    assert cell.reference.NAME == "qwen3_moe stub"
    assert Path(cell.reference.__file__) == (
        tmp_path / "bench/reference/qwen3_moe.py").resolve()
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p90_ms", "setup_s"}
    cfg = PROG.model_config(cell.config)
    assert (cfg.name, cfg.family) == ("qwen3-moe-30b-a3b", "moe")
    assert (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == (
        2, 64, 32, 512)
    assert cfg.moe == MoEConfig(num_experts=8, top_k=2, expert_ffw=32)
    assert cfg.attention == AttentionConfig(
        num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=1_000_000.0)
    assert cfg.norm == "rmsnorm" and not cfg.tie_embeddings
    assert (cfg.dtype, cfg.param_dtype) == ("bfloat16", "float32")
    after = _bench_files(tmp_path)
    assert {k: after[k] for k in before} == before
    # the olmo cells still find the olmo reference under the same root
    olmo = H.find_cell(new, "olmo1b-serve-chat", root=tmp_path)
    assert Path(olmo.reference.__file__).name == "olmo.py"


@pytest.mark.parametrize("reference", [None, "def init_weights(c, seed):\n"
                                       "    return {}\n"])
def test_a_configuration_without_a_whole_reference_is_refused(
        tmp_path, bench, reference):
    """No reference file for the architecture, or one that lacks part of
    the interface: ``find_cell`` refuses the cell."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if reference is not None:
        (tmp_path / "bench/reference/halfdone.py").write_text(reference)
    config = {"model": "x", "architecture": "halfdone",
              "program": {"config": "olmo-1b", "replace": {}},
              "vocab_size": 50304, "param_dtype": "float32",
              "compute_dtype": "bfloat16"}
    new = _add_config(tmp_path, bench, "halfdone", config)
    error = FileNotFoundError if reference is None else AttributeError
    with pytest.raises(error, match="halfdone"):
        H.find_cell(new, "halfdone-serve", root=tmp_path)


def test_every_reference_has_the_interface_and_none_imports_the_program():
    import ast
    from bench.reference import INTERFACE
    files = sorted((ROOT / "bench/reference").glob("*.py"))
    files = [f for f in files if f.name != "__init__.py"]
    assert files
    for f in files:
        mod = H.reference(f.stem)
        assert all(callable(getattr(mod, name, None)) for name in INTERFACE)
        for node in ast.walk(ast.parse(f.read_text())):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import) else
                     [node.module or ""]
                     if isinstance(node, ast.ImportFrom) else [])
            for n in names:
                assert n.split(".")[0] != "repro" and n != "bench.program", \
                    (f, n)


def test_an_unknown_program_config_fails_before_the_chip(tmp_path):
    """A configuration naming what the program's registry lacks (as a new
    one does on the parent of the change that adds it) stops the run
    before it looks for a chip."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    (tmp_path / "src").symlink_to(ROOT / "src")
    path = tmp_path / "bench/configs/olmo-1b.json"
    cfg = json.loads(path.read_text())
    cfg["program"]["config"] = "no-such-model"
    path.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match="no-such-model"):
        PROG.model_config(cfg)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no-such-model" in p.stderr and "bench: needs" not in p.stderr


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


def _published_keys_match(c: dict) -> None:
    """The published keys of configuration file ``c`` against the program
    configuration it names, field by field."""
    cfg = PROG.model_config(c)
    a = cfg.attention
    assert {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "num_attention_heads": a.num_heads,
            "num_key_value_heads": a.num_kv_heads,
            "max_position_embeddings": cfg.max_seq_len,
            "rope_theta": a.rope_theta, "hidden_act": cfg.act,
            "tie_word_embeddings": cfg.tie_embeddings} == {
        k: c[k] for k in ("num_hidden_layers", "hidden_size",
                          "intermediate_size", "vocab_size",
                          "num_attention_heads", "num_key_value_heads",
                          "max_position_embeddings", "rope_theta",
                          "hidden_act", "tie_word_embeddings")}
    assert c["hidden_size"] // c["num_attention_heads"] == a.head_dim
    assert (cfg.dtype, cfg.param_dtype) == (c["compute_dtype"],
                                            c["param_dtype"])


def test_configs_are_the_programs_olmo_1b():
    from repro.configs import registry
    olmo = registry.get_config("olmo-1b")
    serve = json.load(open(ROOT / "bench/configs/olmo-1b.json"))
    train = json.load(open(ROOT / "bench/configs/olmo-1b-4l.json"))
    assert PROG.model_config(serve) == olmo
    assert PROG.model_config(train) == olmo.replace(num_layers=4)
    for c in (serve, train):
        _published_keys_match(c)
    changed = {k for k in serve if serve[k] != train.get(k)} - {
        "reduced", "published", "deployment", "program"}
    assert changed == set(train["reduced"]) == {"num_hidden_layers"}


def test_tiny_config_is_the_program_config_it_names():
    import bench_tiny as tiny
    _published_keys_match(tiny.TINY)
    assert PROG.model_config(tiny.TINY).name == tiny.TINY["model"]


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
