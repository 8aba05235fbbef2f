"""The benchmark's float32 references against the program, at the reduced
olmo-1b size on the CPU (Pallas kernels in interpret mode)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program as PROG
from bench.reference import olmo as REF
from repro.configs import registry
from repro.models import api
from repro.models import transformer as TF
from repro.parallel.context import LOCAL

import bench_tiny as tiny

SEED = 2**34 + 3


def reduced_config() -> dict:
    cfg = registry.get_reduced("olmo-1b")
    a = cfg.attention
    program = {"config": "olmo-1b", "replace": {
        "num_layers": cfg.num_layers, "d_model": cfg.d_model,
        "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "max_seq_len": cfg.max_seq_len, "attention.num_heads": a.num_heads,
        "attention.num_kv_heads": a.num_kv_heads,
        "attention.head_dim": a.head_dim}}
    return dict(tiny.TINY, model=cfg.name, program=program,
                num_hidden_layers=cfg.num_layers,
                hidden_size=cfg.d_model, intermediate_size=cfg.d_ff,
                num_attention_heads=a.num_heads,
                num_key_value_heads=a.num_kv_heads,
                vocab_size=cfg.vocab_size,
                max_position_embeddings=cfg.max_seq_len,
                initializer_range=cfg.d_model ** -0.5)


@pytest.fixture(scope="module")
def served():
    """Prefill a prompt, then decode greedily through the cache with the
    Pallas paged kernel; the logits of every position and the tokens."""
    c = reduced_config()
    cfg = PROG.model_config(c)
    assert cfg == registry.get_reduced("olmo-1b").replace(
        name=cfg.name, max_seq_len=cfg.max_seq_len)
    w = REF.init_weights(c, SEED)
    ctx = dataclasses.replace(LOCAL, decode_attn="paged", decode_kv_block=32)
    prompt = np.random.default_rng(0).integers(
        0, c["vocab_size"], 24).astype(np.int32)
    lg, cache = api.prefill(cfg, w, {"tokens": jnp.asarray(prompt)[None]},
                            ctx, max_len=64)
    got = [np.asarray(lg[0])]
    toks = [int(np.argmax(got[-1]))]
    lens = jnp.array([len(prompt)], jnp.int32)
    for _ in range(15):
        lg, cache, lens = TF.decode_step_paged(
            cfg, w, cache, jnp.array([toks[-1]], jnp.int32), lens,
            jnp.array([True]), ctx)
        got.append(np.asarray(lg[0]))
        toks.append(int(np.argmax(got[-1])))
    return c, w, prompt, np.stack(got), np.array(toks, np.int32)


def _reference_logits(c, w, prompt, toks, quant=None):
    seq = jnp.asarray(np.concatenate([prompt, toks[:-1]]))
    flat = REF.flatten(w)
    with jax.default_matmul_precision("highest"):
        x = REF.hidden(c, flat, seq, quant)
        return np.asarray(REF.logits(c, flat, x, quant))[len(prompt) - 1:]


def test_weights_have_the_programs_tree():
    c = reduced_config()
    want = jax.eval_shape(lambda: api.init_params(
        PROG.model_config(c), jax.random.PRNGKey(0)))
    got = REF.init_weights(c, SEED)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_prefill_and_cached_decode_match_the_reference(served):
    """bf16 matrix products with f32 accumulation leave about 0.06 of a
    logit whose spread is 1 (the logits' scale is set by
    ``initializer_range``); 0.15 leaves room.  Rounding every product's
    operands to float8 instead must miss by at least three times what the
    program misses."""
    c, w, prompt, got, toks = served
    ref = _reference_logits(c, w, prompt, toks)
    assert 0.5 < ref.std() < 2
    err = np.abs(got - ref).max()
    assert err < 0.15
    fp8 = np.abs(_reference_logits(c, w, prompt, toks, "fp8") - ref).max()
    assert fp8 > 3 * err


def test_served_gaps_read_the_greedy_tokens(served):
    c, w, prompt, got, toks = served
    gaps, _ = REF.served_gaps(c, w, prompt, toks, 48, 20)
    assert gaps.shape == toks.shape and np.all(gaps >= 0)
    assert gaps.max() < 0.15
    wrong = toks.copy()
    wrong[5] = np.argmin(got[5])
    gaps, _ = REF.served_gaps(c, w, prompt, wrong, 48, 20)
    assert gaps[5] > 1.0


def test_train_steps_match_the_reference():
    """Three program steps against three reference steps from the same
    weights on the same batches (the train cell's own comparison)."""
    out = tiny.run(tiny.train_cell(reduced_config()), control=True)
    assert not out.correct, out.checks
    prog = out.info["program"]
    assert max(out.info["loss_gaps"]) < 0.02
    for k in ("first_grad_gap", "update_gap", "update_gap_median"):
        assert prog[k] < 0.02
    assert out.checks["first_grad_gap"]["value"] > \
        3 * prog["first_grad_gap"]
