"""Per-layer mixer schedules (lfm2): short-conv and attention layers, a
dense-FFN prefix and held-expert MoE layers, served on the pooled layout.

Program-only properties at a reduced size on the CPU; the comparison with
the plain float32 reference is in ``tests/bench/test_bench_lfm2.py``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import api
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import transformer as TF
from repro.obs import Telemetry
from repro.serve.engine import ServeEngine, SliceSpec


def _held(cfg, held=4, first=0):
    return cfg.replace(moe=dataclasses.replace(cfg.moe, held_experts=held,
                                               first_expert=first))


@pytest.fixture(scope="module")
def model():
    cfg = _held(registry.get_reduced("lfm2-8b-a1b"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    # a drawn expert bias, so that the choice is the bias's as well
    for lp in params["layers"]:
        if "moe" in lp:
            lp["moe"]["expert_bias"] = 0.3 * jax.random.normal(
                jax.random.PRNGKey(1), lp["moe"]["expert_bias"].shape)
    return cfg, params


def test_segments_follow_the_published_schedule():
    cfg = registry.get_config("lfm2-8b-a1b")
    segs = TF.segments(cfg)
    assert sum(s.n for s in segs) == 24 and len(segs) == 13
    assert [s.start for s in segs if s.mixer == "attention"] == [
        2, 6, 10, 14, 18, 21]
    assert segs[0] == TF.Segment("conv", "mlp", 0, 2, 0)
    assert all(s.ffn == "moe" for s in segs[1:])
    assert [s.slot for s in segs if s.mixer == "attention"] == list(range(6))
    assert TF.num_attention_layers(cfg) == 6 and cfg.conv_layers == 18
    assert 8.2e9 < cfg.param_count() < 8.5e9


def test_short_conv_state_carries_across_splits_and_steps(model):
    """A sequence convolved whole, in two pieces, or a token at a time
    through the carried state gives the same rows, bit for bit."""
    cfg, params = model
    lp = jax.tree.map(lambda t: t[0], params["layers"][0]["conv"])
    B, T, D = 2, 11, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, D)).astype(
        jnp.bfloat16)
    zero = jnp.zeros((B, cfg.conv_width - 1, D), jnp.bfloat16)
    whole, st = SSM.short_conv(cfg, lp, x, zero, jnp.full((B,), T))
    a, st_a = SSM.short_conv(cfg, lp, x[:, :5], zero, jnp.full((B,), 5))
    b, st_b = SSM.short_conv(cfg, lp, x[:, 5:], st_a, jnp.full((B,), T - 5))
    np.testing.assert_array_equal(np.concatenate([a, b], 1), whole)
    np.testing.assert_array_equal(st_b, st)
    s, rows = zero, []
    for t in range(T):
        y, s = SSM.short_conv(cfg, lp, x[:, t:t + 1], s, jnp.ones((B,), int))
        rows.append(y)
    np.testing.assert_array_equal(np.concatenate(rows, 1), whole)
    # a row with nothing valid keeps its state
    _, kept = SSM.short_conv(cfg, lp, x[:, :1], st, jnp.array([0, 1]))
    np.testing.assert_array_equal(kept[0], st[0])


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of a 4-way expert-parallel deployment, each holding 4 of
    the 16 experts: their partial results add up to the layer that holds
    all 16, and the routed pairs partition."""
    cfg = registry.get_reduced("lfm2-8b-a1b")
    full = _held(cfg, held=16)
    p = jax.tree.map(lambda t: t[0], TF.init_params(full, jax.random.PRNGKey(
        4))["layers"][1]["moe"])
    p["expert_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    x = jax.random.normal(jax.random.PRNGKey(6), (24, cfg.d_model)).astype(
        jnp.bfloat16)
    uncut, routed = MOE.moe_held(full, p, x)
    parts, pairs = [], 0
    for j in range(4):
        share = {k: (v[4 * j:4 * j + 4] if k in ("wg", "wu", "wo") else v)
                 for k, v in p.items()}
        out, r = MOE.moe_held(_held(cfg, 4, 4 * j), share, x)
        parts.append(np.asarray(out, np.float32))
        np.testing.assert_array_equal(r, routed[:, 4 * j:4 * j + 4])
        pairs += int(r.sum())
    assert pairs == 24 * cfg.moe.top_k
    # each share rounds its own partial sum to bf16 once
    np.testing.assert_allclose(sum(parts), np.asarray(uncut, np.float32),
                               atol=0.02, rtol=0.02)


def test_the_expert_bias_moves_the_choice_not_the_gates():
    cfg = registry.get_reduced("lfm2-8b-a1b")
    m = cfg.moe
    x = jax.random.normal(jax.random.PRNGKey(7), (64, cfg.d_model)).astype(
        jnp.bfloat16)
    p = {"router": jax.random.normal(jax.random.PRNGKey(8),
                                     (cfg.d_model, m.num_experts)) * 0.125,
         "expert_bias": jnp.zeros((m.num_experts,))}
    g0, e0, aux = MOE.router_topk(cfg, p, x)
    assert float(aux) == 0.0
    p["expert_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9),
                                               (m.num_experts,))
    g1, e1, _ = MOE.router_topk(cfg, p, x)
    assert np.any(np.sort(e0, -1) != np.sort(e1, -1))
    logits = jnp.einsum("sd,de->se", x, p["router"].astype(jnp.bfloat16)
                        ).astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    want = jnp.take_along_axis(scores, e1, -1)
    np.testing.assert_allclose(g1, want / want.sum(-1, keepdims=True),
                               rtol=1e-6)
    _, top = jax.lax.top_k(scores + p["expert_bias"], m.top_k)
    np.testing.assert_array_equal(e1, top)


def _pool(cfg, slots=3, nb=4, bs=8, blocks=16):
    cache = api.init_kv_pool(cfg, blocks, bs, slots=slots)
    tables = np.full((slots, nb), blocks, np.int32)
    for s in range(slots):
        tables[s] = np.arange(s * nb, (s + 1) * nb) % blocks
    return cache, jnp.asarray(tables)


def _prefill(cfg, params, cache, tables, prompts, width=8):
    """Admit ``prompts`` (one per slot) in ``width``-token dispatches."""
    slots = tables.shape[0]
    for c0 in range(0, max(len(p) for p in prompts), width):
        tok = np.zeros((slots, width), np.int32)
        st, vd = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        for s, p in enumerate(prompts):
            v = max(0, min(width, len(p) - c0))
            tok[s, :v], st[s], vd[s] = p[c0:c0 + v], min(c0, len(p)), v
        _, cache = api.prefill_suffix(cfg, params, cache, jnp.asarray(tok),
                                      jnp.asarray(st), jnp.asarray(vd),
                                      tables)
    return cache


@pytest.fixture(scope="module")
def admitted(model):
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (13, 5, 9)]
    cache, tables = _pool(cfg)
    cache = _prefill(cfg, params, cache, tables, prompts)
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    toks = jnp.asarray([int(p[-1]) for p in prompts], jnp.int32)
    return cache, tables, lens, toks


def test_chunk_size_leaves_tokens_and_state_alone(model, admitted):
    """Dropless: eight one-step chunks and one eight-step chunk give the
    same tokens, the same pool and conv state, and the same next logits."""
    cfg, params = model
    cache, tables, lens, toks = admitted
    budget = jnp.asarray([8, 6, 8], jnp.int32)
    decode = jax.jit(lambda p, c, t, n, b, steps: api.decode_n(
        cfg, p, c, t, n, b, num_steps=steps, tables=tables, moe_load=True),
        static_argnums=5)
    one = decode(params, cache, toks, lens, budget, 8)
    c, t, n, got = cache, toks, lens, []
    for i in range(8):
        out, c, n, t, _ = decode(params, c, t, n, jnp.maximum(budget - i, 0),
                                 1)
        got.append(out)
    np.testing.assert_array_equal(np.concatenate(got), one[0])
    for x, y in ((c.k, one[1].k), (c.v, one[1].v), (c.conv, one[1].conv)):
        np.testing.assert_array_equal(x, y)
    flat = lambda c_: (c_.k.reshape((-1,) + c_.k.shape[2:]),
                       c_.v.reshape((-1,) + c_.v.shape[2:]), c_.conv)
    step = jax.jit(lambda p, kv, t, n: TF.decode_step_pooled(
        cfg, p, kv, t, n, jnp.ones((3,), bool), tables)[0])
    np.testing.assert_array_equal(step(params, flat(c), t, n),
                                  step(params, flat(one[1]), one[3], one[2]))
    # per step and MoE layer: pairs of live slots, held experts touched
    assert one[4].shape == (8, 4, 2)
    assert np.all(one[4][..., 1] <= one[4][..., 0])
    assert np.all(one[4][..., 0] <= 3 * cfg.moe.top_k)


def test_a_slots_logits_ignore_the_other_slots(model, admitted):
    """Dropless: slot 0's logits are the same whether the other slots are
    live on other tokens or idle."""
    cfg, params = model
    cache, tables, lens, toks = admitted
    kv = (cache.k.reshape((-1,) + cache.k.shape[2:]),
          cache.v.reshape((-1,) + cache.v.shape[2:]), cache.conv)
    step = jax.jit(lambda p, kv, t, n, act: TF.decode_step_pooled(
        cfg, p, kv, t, n, act, tables))
    live = step(params, kv, toks, lens, jnp.asarray([True, True, True]))
    alone = step(params, kv, toks.at[1:].set(7), lens,
                 jnp.asarray([True, False, False]))
    np.testing.assert_array_equal(live[0][0], alone[0][0])
    np.testing.assert_array_equal(live[1][2][:, 0], alone[1][2][:, 0])
    # the idle slots' conv state did not move
    np.testing.assert_array_equal(alone[1][2][:, 1:], cache.conv[:, 1:])
    assert int(alone[3][:, 0].max()) <= cfg.moe.top_k


def test_engine_serves_and_counts_the_held_expert_load(model):
    cfg, params = model
    obs = Telemetry(tracing=True)
    eng = ServeEngine(cfg, params, SliceSpec(
        slots=2, max_len=32, prompt_len=16, chunk=4, kv_block=8,
        kv_share=False, kv_blocks=8, suffix_len=8), obs=obs)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=6)
            for n in (12, 3, 7)]
    eng.run()
    assert all(r.done and len(r.out_tokens) == 6 for r in reqs)
    spans = [s for s in obs.tracer.spans if s.name == "serve.decode.moe"]
    assert spans and all(set(s.args) >= {"pairs", "touched", "steps"}
                         for s in spans)
    pairs = obs.metrics.counter("serve.moe_pairs").value
    touched = obs.metrics.counter("serve.moe_experts_touched").value
    assert pairs == sum(s.args["pairs"] for s in spans) > 0
    assert 0 < touched <= pairs


@pytest.mark.parametrize("spec,match", [
    (dict(kv_block=8, kv_share=True), "kv_share=False"),
    (dict(kv_block=0), "kv_block > 0")], ids=["kv_share", "per_slot"])
def test_conv_state_refuses_layouts_that_cannot_carry_it(model, spec, match):
    cfg, params = model
    with pytest.raises(ValueError, match=match):
        ServeEngine(cfg, params, SliceSpec(slots=2, max_len=32,
                                           prompt_len=16, **spec))


def test_legacy_cache_paths_refuse_a_schedule(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="pooled KV layout"):
        api.init_cache(cfg, 2, 32)
    with pytest.raises(NotImplementedError, match="pooled KV layout"):
        api.prefill(cfg, params, {"tokens": jnp.zeros((1, 4), jnp.int32)})


def test_dense_pooled_decode_reports_no_load():
    cfg = registry.get_reduced("olmo-1b")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    cache = api.init_kv_pool(cfg, 8, 8)
    tables = jnp.asarray(np.arange(8, dtype=np.int32).reshape(2, 4))
    out = api.decode_n(cfg, params, cache, jnp.zeros((2,), jnp.int32),
                       jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32),
                       num_steps=2, tables=tables, moe_load=True)
    assert len(out) == 5 and out[4] is None and out[1].conv is None
    assert len(api.decode_n(cfg, params, cache, out[3], out[2],
                            jnp.ones((2,), jnp.int32), num_steps=1,
                            tables=tables)) == 4


def test_one_row_admission_keeps_the_other_slots_conv_state(model,
                                                            admitted):
    """A prompt admitted into slot 2 of 3 by one-row dispatches (row ->
    slot through ``slots``) leaves slots 0 and 1's conv state bit for bit,
    and gives the tokens of the slot-aligned admission."""
    cfg, params = model
    cache, tables, lens, toks = admitted
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, 19).astype(np.int32)
    prefill = jax.jit(lambda c, t, s, v, tb, rows: api.prefill_suffix(
        cfg, params, c, t, s, v, tb, slots=rows))

    def admit(c, aligned):
        rows = 3 if aligned else 1
        row = 2 if aligned else 0
        for c0 in range(0, len(prompt), 8):
            v = min(8, len(prompt) - c0)
            tok = np.zeros((rows, 8), np.int32)
            st, vd = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
            tok[row, :v], st[row], vd[row] = prompt[c0:c0 + v], c0, v
            logits, c = prefill(
                c, jnp.asarray(tok), jnp.asarray(st), jnp.asarray(vd),
                tables if aligned else tables[2:3],
                None if aligned else jnp.asarray([2], jnp.int32))
        return int(jnp.argmax(logits[row])), c

    first, one = admit(cache, aligned=False)
    np.testing.assert_array_equal(one.conv[:, :2], cache.conv[:, :2])
    assert np.any(np.asarray(one.conv[:, 2]) != np.asarray(cache.conv[:, 2]))
    want, aligned = admit(cache, aligned=True)
    assert first == want
    # and the decode that follows, on slot 2
    n = lens.at[2].set(len(prompt))
    t = toks.at[2].set(first)
    budget = jnp.asarray([0, 0, 6], jnp.int32)
    got = [api.decode_n(cfg, params, c, t, n, budget, num_steps=6,
                        tables=tables)[0][:, 2] for c in (one, aligned)]
    np.testing.assert_array_equal(got[0], got[1])
