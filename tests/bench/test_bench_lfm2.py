"""LFM2-MoE: the benchmark's configuration against the program's, and the
float32 reference (``bench/reference/lfm2_moe.py``) against the program at
a small size on the CPU (Pallas kernels in interpret mode): d 64, 16
experts of which 4 are held, top-4, a schedule of both layer kinds and 2
dense layers.

The tolerances are in logits whose spread is 1 (``initializer_range`` sets
the scale).  bf16 matrix products with f32 accumulation leave about 0.05
at most positions.  Routing is discrete: where two experts' biased scores
lie within rounding of each other, bf16 can pick the other one, and that
position (with the next two, through the convolutions) reads up to about
0.4.  So the typical position is held to 0.1 and the worst to 0.6;
rounding every product's operands to float8 instead must move the typical
position by three times what the program moves it.
"""
import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import program as PROG
from bench.reference import lfm2_moe as REF
from repro.models import api
from repro.models import moe as MOE
from repro.models import transformer as TF

import bench_tiny as tiny

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**34 + 11
C, A = "conv", "full_attention"
TYPES = [C, C, A, C, C, A]

TINY = {"model": "lfm2-tiny", "architecture": "lfm2_moe",
        "program": {"config": "lfm2-8b-a1b", "replace": {
            "name": "lfm2-tiny", "num_layers": 6, "d_model": 64,
            "d_ff": 96, "vocab_size": 512, "max_seq_len": 128,
            "mixers": ["attention" if t == A else t for t in TYPES],
            "attention.num_heads": 4, "attention.num_kv_heads": 2,
            "attention.head_dim": 16, "moe.num_experts": 16,
            "moe.expert_ffw": 32, "moe.dense_ffw": 96,
            "moe.held_experts": 4}},
        "layer_types": TYPES, "num_hidden_layers": 6, "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_experts": 4, "num_experts_per_tok": 4, "num_dense_layers": 2,
        "published": {"num_experts": 16}, "conv_L_cache": 3,
        "norm_eps": 1e-5, "rope_theta": 1000000, "routed_scaling_factor": 1,
        "vocab_size": 512, "max_position_embeddings": 128,
        "use_expert_bias": True, "norm_topk_prob": True, "conv_bias": False,
        "tie_word_embeddings": True, "hidden_act": "silu",
        "initializer_range": 0.125, "param_dtype": "bfloat16",
        "compute_dtype": "bfloat16"}

TYPICAL, WORST = 0.1, 0.6


def _published_keys_match(c: dict) -> None:
    """The published keys of an LFM2-MoE configuration file against the
    program configuration it names, field by field."""
    cfg = PROG.model_config(c)
    a, m = cfg.attention, cfg.moe
    mixers = ["attention" if t == "full_attention" else t
              for t in c["layer_types"]]
    assert {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
            "intermediate_size": m.dense_ffw, "vocab_size": cfg.vocab_size,
            "num_attention_heads": a.num_heads,
            "num_key_value_heads": a.num_kv_heads,
            "rope_theta": a.rope_theta, "norm_eps": cfg.norm_eps,
            "conv_L_cache": cfg.conv_width,
            "moe_intermediate_size": m.expert_ffw,
            "num_experts_per_tok": m.top_k,
            "num_dense_layers": m.dense_layers,
            "num_experts": MOE.held_experts(m),
            "use_expert_bias": m.expert_bias} == {
        k: c[k] for k in ("num_hidden_layers", "hidden_size",
                          "intermediate_size", "vocab_size",
                          "num_attention_heads", "num_key_value_heads",
                          "rope_theta", "norm_eps", "conv_L_cache",
                          "moe_intermediate_size", "num_experts_per_tok",
                          "num_dense_layers", "num_experts",
                          "use_expert_bias")}
    assert list(cfg.mixers) == mixers and cfg.d_ff == c["intermediate_size"]
    assert m.num_experts == c["published"]["num_experts"]
    assert c["hidden_size"] // c["num_attention_heads"] == a.head_dim
    # what the program computes and the file cannot vary
    assert a.qk_norm and m.score == "sigmoid" and cfg.norm == "rmsnorm"
    assert (c["norm_topk_prob"], c["routed_scaling_factor"],
            c["conv_bias"]) == (True, 1, False)
    assert cfg.tie_embeddings == c["tie_word_embeddings"] and cfg.act == \
        c["hidden_act"] == "silu"
    assert (cfg.dtype, cfg.param_dtype) == (c["compute_dtype"],
                                            c["param_dtype"])


def test_config_file_is_the_programs_lfm2_at_one_chips_share():
    c = json.load(open(ROOT / "bench/configs/lfm2-8b-a1b-ep4.json"))
    _published_keys_match(c)
    cfg = PROG.model_config(c)
    assert cfg.num_layers == 24 and cfg.moe.first_expert == 0
    assert (cfg.d_model, cfg.attention.head_dim, cfg.moe.expert_ffw,
            cfg.moe.num_experts, cfg.vocab_size) == (2048, 64, 1792, 32,
                                                     65536)
    assert c["reduced"] == ["num_experts"] and c["num_experts"] == 8
    assert c["published"] == {"num_experts": 32}
    _published_keys_match(TINY)


def test_weights_have_the_programs_tree():
    cfg = PROG.model_config(TINY)
    want = jax.eval_shape(lambda: api.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    got = REF.init_weights(TINY, SEED)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
    assert set(REF.flatten(got)) == set(REF.leaf_shapes(TINY))


def _reference_logits(c, w, seq, quant=None):
    flat = REF.flatten(w)
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.logits(c, flat, REF.hidden(
            c, flat, jnp.asarray(seq), quant), quant))


def _errors(got, want):
    e = np.abs(np.asarray(got, np.float32) - want).max(-1)
    return float(np.median(e)), float(e.max())


@pytest.fixture(scope="module")
def served():
    """A 21-token prompt prefilled through the pool in 8-token dispatches
    (so the conv state crosses two dispatch boundaries), then 12 greedy
    steps through the cache with the Pallas block-table kernel: the logits
    of every position and the tokens."""
    import dataclasses
    from repro.parallel.context import LOCAL
    cfg = PROG.model_config(TINY)
    w = REF.init_weights(TINY, SEED)
    ctx = dataclasses.replace(LOCAL, decode_attn="paged")
    prompt = np.random.default_rng(1).integers(0, 512, 21).astype(np.int32)
    bs, nb, slots, Tc = 8, 8, 2, 8
    cache = api.init_kv_pool(cfg, 16, bs, slots=slots)
    tables = jnp.asarray(np.arange(16, dtype=np.int32).reshape(slots, nb))
    prefill = jax.jit(lambda w, c, t, s, v: api.prefill_suffix(
        cfg, w, c, t, s, v, tables, ctx))
    step = jax.jit(lambda w, kv, t, n: TF.decode_step_pooled(
        cfg, w, kv, t, n, jnp.asarray([False, True]), tables, ctx))
    for c0 in range(0, len(prompt), Tc):
        v = min(Tc, len(prompt) - c0)
        tok = np.zeros((slots, Tc), np.int32)
        tok[1, :v] = prompt[c0:c0 + v]
        lg, cache = prefill(w, cache, jnp.asarray(tok), jnp.asarray([0, c0]),
                            jnp.asarray([0, v]))
    got = [np.asarray(lg[1])]
    toks = [int(np.argmax(got[-1]))]
    kv = (cache.k.reshape((-1,) + cache.k.shape[2:]),
          cache.v.reshape((-1,) + cache.v.shape[2:]), cache.conv)
    lens = jnp.asarray([0, len(prompt)], jnp.int32)
    for _ in range(12):
        lg, kv, lens, _ = step(w, kv, jnp.asarray([0, toks[-1]], jnp.int32),
                               lens)
        got.append(np.asarray(lg[1]))
        toks.append(int(np.argmax(got[-1])))
    return w, prompt, np.stack(got), np.array(toks, np.int32)


def test_forward_matches_the_reference():
    cfg = PROG.model_config(TINY)
    w = REF.init_weights(TINY, SEED)
    seq = np.random.default_rng(2).integers(0, 512, 40).astype(np.int32)
    got, _ = api.forward(cfg, w, {"tokens": jnp.asarray(seq)[None]})
    ref = _reference_logits(TINY, w, seq)
    assert 0.5 < ref.std() < 2
    typical, worst = _errors(got[0], ref)
    assert typical < TYPICAL and worst < WORST
    fp8, _ = _errors(_reference_logits(TINY, w, seq, "fp8"), ref)
    assert fp8 > 3 * typical


def test_pooled_prefill_and_decode_match_the_reference(served):
    w, prompt, got, toks = served
    seq = np.concatenate([prompt, toks[:-1]])
    ref = _reference_logits(TINY, w, seq)[len(prompt) - 1:]
    typical, worst = _errors(got, ref)
    assert typical < TYPICAL and worst < WORST
    fp8 = _reference_logits(TINY, w, seq, "fp8")[len(prompt) - 1:]
    assert _errors(fp8, ref)[0] > 3 * typical


def test_served_gaps_read_the_greedy_tokens(served):
    w, prompt, got, toks = served
    gaps, _ = REF.served_gaps(TINY, w, prompt, toks, 48, 20)
    assert gaps.shape == toks.shape and np.all(gaps >= 0)
    assert gaps.max() < WORST
    wrong = toks.copy()
    wrong[5] = np.argmin(got[5])
    gaps, _ = REF.served_gaps(TINY, w, prompt, wrong, 48, 20)
    assert gaps[5] > 1.0


def test_train_steps_match_the_reference():
    """Three program steps through ``Slice.train`` against three reference
    steps from the same float32 weights on the same batches: the losses and
    the first gradient's leaf norms."""
    config = dict(copy.deepcopy(TINY), param_dtype="float32")
    out = tiny.run(tiny.train_cell(config), control=True)
    prog = out.info["program"]
    assert max(out.info["loss_gaps"]) < 0.02
    assert prog["first_grad_gap"] < 0.02
    assert out.checks["first_grad_gap"]["value"] > 2 * prog["first_grad_gap"]


def _reason_cell():
    """``lfm2-8b-serve-reason``'s driver and limits on the tiny model and
    the tiny mix; the sample's widest gap held to ``WORST``."""
    reason = H.find_cell(H.load_benchmark(), "lfm2-8b-serve-reason")
    cell = tiny.serve_cell(copy.deepcopy(TINY), kind=reason.mix["kind"])
    cell.limits = dict(reason.limits, sample_requests=3, max_logit_gap=WORST)
    return cell


def test_the_serve_cell_runs_through_the_engine_and_is_correct():
    """The cell's driver at a tiny size: requests through ``Slice.serve``
    on the pooled layout (prompts longer than one prefill dispatch), every
    served token checked against the reference."""
    out = tiny.run(_reason_cell())
    assert out.correct, out.checks
    assert set(out.checks) == {"max_logit_gap", "share_above_margin"}
    assert out.info["completed"] > 0 and out.failed == 0
    assert out.info["checked_tokens"] > 0


def test_the_control_fails_the_reason_cell_by_the_share():
    """The float8 control's choices are wide of the reference's best on
    far more tokens than the program's: the run is not correct by the
    share, while its widest gap stays under the cell's own limit (as
    routing flips keep it on the chip)."""
    cell = _reason_cell()
    limits = H.find_cell(H.load_benchmark(), "lfm2-8b-serve-reason").limits
    out = tiny.run(cell, control=True)
    share = out.checks["share_above_margin"]
    assert not out.correct and share["value"] > share["limit"]
    assert out.checks["max_logit_gap"]["value"] < limits["max_logit_gap"]
    assert out.info["program_share_above_margin"] < share["limit"]
    assert out.info["program_max_logit_gap"] < WORST


class _Gaps:
    """A reference whose gaps are given: the program's and the control's
    per served token."""

    def __init__(self, gaps, cgaps):
        self.gaps, self.cgaps = gaps, cgaps

    def served_gaps(self, c, weights, prompt, served, width, n_out,
                    control=False):
        return self.gaps[:len(served)], self.cgaps[:len(served)]


@pytest.mark.parametrize("control", [False, True])
def test_routed_check_holds_the_share_and_the_widest_gap(control):
    """Every tenth token 0.3 wide of the best reads a share of 0.1, which
    is the limit and passes; the control, wide on half its tokens, fails
    by the share alone."""
    from types import SimpleNamespace
    from bench.drivers import serve_routed as SR
    gaps = np.where(np.arange(100) % 10 == 0, 0.3, 0.0)
    cgaps = np.where(np.arange(100) % 2 == 0, 0.3, 0.0)
    limits = {"sample_requests": 2, "gap_margin": 0.1,
              "share_above_margin": 0.1, "max_logit_gap": 2.5}
    job = SimpleNamespace(seed=2**40 + 3, control=control,
                          cell=SimpleNamespace(limits=limits,
                                               reference=_Gaps(gaps, cgaps)))
    served = {k: np.arange(100, dtype=np.int32) for k in range(4)}
    reqs = SimpleNamespace(prompts={k: np.zeros(8, np.int32)
                                    for k in range(4)})
    checks, sample, readings = SR._check(job, {"vocab_size": 512}, reqs,
                                         served, [0, 1, 2, 3], 120, 100,
                                         None)
    assert len(sample) == 2
    assert checks["max_logit_gap"]["value"] == pytest.approx(0.3)
    share = checks["share_above_margin"]["value"]
    assert share == pytest.approx(0.5 if control else 0.1)
    if control:
        assert readings["program_share_above_margin"] == pytest.approx(0.1)


def test_the_routed_driver_leaves_the_open_loop_check_to_the_others():
    """``serve_routed`` runs ``serve_open_loop``'s window with its own
    check; the chat cell's driver keeps the widest gap alone."""
    from bench.drivers import serve_open_loop as SOL
    from bench.drivers import serve_routed as SR
    assert SR.run.__globals__["_check"] is SR._check
    assert SOL.run.__globals__["_check"] is SOL._check
    assert H.find_cell(H.load_benchmark(),
                       "olmo1b-serve-chat").mix["kind"] == "serve_open_loop"


def test_the_reason_cell_finds_its_files():
    cell = H.find_cell(H.load_benchmark(), "lfm2-8b-serve-reason")
    assert cell.reference.__file__ == REF.__file__ and cell.chips == 1
    assert cell.config == json.load(
        open(ROOT / "bench/configs/lfm2-8b-a1b-ep4.json"))
    assert callable(H.driver(cell.mix["kind"]).run)
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p90_ms",
                                                    "setup_s"}
    # not the dense per-layer counts, which take every layer for attention
    # and every expert for a dense MLP
    assert {m["name"] for m in cell.per_layer} == {
        "serve.ttft_p90_ms", "serve.prefill_share", "serve.decode_chunk_ms",
        "device.idle_share.serve", "serve.prefill_token_use",
        "serve.host_stall_ms", "serve.compiles_in_window",
        "moe.decode_roofline"}
    e = cell.mix["engine"]
    assert (e["slots"], e["max_len"], e["prompt_len"], e["chunk"],
            e["kv_block"], e["kv_blocks"], e["suffix_len"], e["kv_share"]) \
        == (12, 2048, 512, 8, 128, 192, 512, False)


def _moe_spans(monkeypatch, moe):
    from types import SimpleNamespace
    from bench import spans as SP
    sp = SimpleNamespace(named=lambda name: moe if name == "serve.decode.moe"
                         else [])
    monkeypatch.setattr(SP, "for_job", lambda job: sp)


def test_moe_roofline_reads_nothing_without_the_engines_moe_spans(
        monkeypatch):
    """A run that traced nothing, or a program that reports no held-expert
    load (the parent's, or a dense model's), leaves the metric out."""
    from types import SimpleNamespace
    reader = H.metric_reader("moe.decode_roofline")
    job = SimpleNamespace(tracer=SimpleNamespace(dir=None))
    assert reader.read(None, job, None) is None
    _moe_spans(monkeypatch, [])
    assert reader.read(None, job, None) is None


def test_moe_roofline_is_the_least_time_over_the_decode_time(monkeypatch):
    """One chunk of 8 steps with 10 live requests: the share falls as the
    decode program's time grows and rises with the experts it touched,
    and at the chip's measured ~64 ms a chunk stays under 100%."""
    from types import SimpleNamespace
    from bench.peaks import PEAKS
    from bench.spans import Span
    reader = H.metric_reader("moe.decode_roofline")
    cell = H.find_cell(H.load_benchmark(), "lfm2-8b-serve-reason")
    chunks = [[[300 + s for s in range(8)] for _ in range(10)]]
    out = SimpleNamespace(records={"decode_chunks": chunks})

    def share(touched, decode_s):
        _moe_spans(monkeypatch, [Span("serve.decode.moe", 0.0, 1.0, {
            "pairs": 8 * 10 * 22, "touched": touched, "steps": 8})])
        job = SimpleNamespace(cell=cell, peaks=PEAKS["TPU v5 lite"])
        summary = SimpleNamespace(program_s=lambda p: [decode_s])
        return reader.read(summary, job, out)

    base = share(8 * 22 * 5, 0.064)
    assert 0 < base < 100
    assert share(8 * 22 * 5, 0.128) == pytest.approx(base / 2)
    assert share(8 * 22 * 8, 0.064) > base
