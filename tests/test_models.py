"""Per-arch smoke tests (reduced configs) + prefill/decode consistency.

The consistency test is the strongest correctness check we have: teacher-
forced forward logits at position t must match prefill(prefix)+decode chain
logits for every family that serves (attention KV caches, SSM states, hybrid
combinations, cross-attention caches).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ShapeConfig
from repro.models import api

SMOKE_TRAIN = ShapeConfig("smoke_train", "train", 64, 2)

ARCHS = list(registry.ALL_ARCHS)


def assert_mostly_close(a, b, rtol=5e-2, atol=1e-1, frac=0.995):
    """bf16-robust closeness: >=frac of elements within tolerance."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    ok = np.abs(a - b) <= (atol + rtol * np.abs(b))
    assert ok.mean() >= frac, (
        f"only {ok.mean():.4f} close; worst={np.abs(a - b).max():.4f}")


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = registry.get_reduced(arch)
    key = jax.random.PRNGKey(0)
    params = api.init_params(cfg, key)
    batch = api.make_batch(cfg, SMOKE_TRAIN, key)
    batch.pop("labels", None)
    logits, aux = api.forward(cfg, params, batch)
    if cfg.family == "dlrm":
        assert logits.shape == (SMOKE_TRAIN.global_batch,)
    else:
        assert logits.shape[0] == SMOKE_TRAIN.global_batch
        assert logits.shape[-1] == cfg.vocab_size
    assert bool(jnp.isfinite(logits).all()), "non-finite logits"
    assert bool(jnp.isfinite(aux)), "non-finite aux loss"


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step_runs_and_loss_finite(arch):
    from repro.configs.base import OptimizerConfig, ParallelConfig
    from repro.launch import steps as STEPS
    from repro.optim import adam as OPT
    from repro.parallel.context import LOCAL

    cfg = registry.get_reduced(arch)
    shape = ShapeConfig("t", "train", 32, 2)
    key = jax.random.PRNGKey(1)
    params = api.init_params(cfg, key)
    ocfg = OptimizerConfig(lr=1e-3)
    opt = OPT.init(ocfg, params)
    batch = api.make_batch(cfg, shape, key)
    step = STEPS.make_train_step(cfg, shape, ParallelConfig(remat="none"),
                                 ocfg, LOCAL, accum_steps=1)
    p2, o2, metrics = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0


# a per-layer mixer schedule serves on the pooled layout only; its pooled
# prefill and decode are checked against the float32 reference in
# tests/bench/test_bench_lfm2.py
@pytest.mark.parametrize("arch", [a for a in ARCHS if a != "dlrm0"
                                  and not registry.get_config(a).mixers])
def test_prefill_decode_matches_forward(arch):
    cfg = registry.get_reduced(arch)
    key = jax.random.PRNGKey(2)
    params = api.init_params(cfg, key)
    T = 24
    shape = ShapeConfig("c", "prefill", T, 2)
    batch = api.make_batch(cfg, shape, key)

    # teacher-forced forward over the full sequence (MoE: high capacity so
    # dropping can't differ between the full-sequence and decode paths)
    kw = {"moe_cf": 16.0} if cfg.family == "moe" else {}
    logits_full, _ = api.forward(cfg, params, batch, **kw)

    # prefill on the first T-4 tokens, then decode the remaining 4
    cut = T - 4
    if cfg.family == "audio":
        from repro.models.whisper import split_seq
        enc, dec = split_seq(cfg, T)
        cut = dec - 4
        pre = dict(batch)
        pre["tokens"] = batch["tokens"][:, :cut]
    elif cfg.family == "vlm":
        pre = dict(batch)
        pre["tokens"] = batch["tokens"][:, :cut - cfg.vision_prefix] \
            if cut > cfg.vision_prefix else dict(batch)["tokens"][:, :2]
        cut = pre["tokens"].shape[1] + cfg.vision_prefix
        logits_full_t = logits_full
    else:
        pre = {k: (v[:, :cut] if k == "tokens" else v)
               for k, v in batch.items()}

    max_len = T + 8
    logits_pre, cache = api.prefill(cfg, params, pre, max_len=max_len, **kw)

    # the prefill's last-position logits must match forward at that position
    assert_mostly_close(logits_pre, logits_full[:, cut - 1])

    # decode the next tokens one by one and compare against forward
    toks = batch["tokens"]
    n_dec = 3
    for i in range(n_dec):
        if cfg.family == "audio":
            nxt = toks[:, cut + i]
        elif cfg.family == "vlm":
            nxt = toks[:, cut - cfg.vision_prefix + i]
        else:
            nxt = toks[:, cut + i]
        logits_dec, cache = api.decode_step(cfg, params, cache, nxt, **kw)
        want = logits_full[:, cut + i]
        assert_mostly_close(logits_dec, want)


def test_gemma2_window_schedule():
    from repro.models.transformer import GLOBAL_WINDOW, window_schedule
    cfg = registry.get_config("gemma2-9b")
    ws = window_schedule(cfg)
    assert len(ws) == 42
    assert ws[0] == 4096 and ws[1] == GLOBAL_WINDOW
    assert (ws[::2] == 4096).all() and (ws[1::2] == GLOBAL_WINDOW).all()


def test_blocked_attention_matches_reference():
    from repro.models.layers import blocked_attention, reference_attention
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    B, T, H, KH, D = 2, 48, 4, 2, 16
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KH, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KH, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    for kw in [dict(), dict(window=8), dict(softcap=20.0),
               dict(causal=False)]:
        got = blocked_attention(q, k, v, pos, pos, kv_chunk=16, **kw)
        want = reference_attention(q, k, v, pos, pos, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)


def test_param_counts_match_published():
    expect = {
        "gemma2-9b": (9.0e9, 9.5e9),
        "olmo-1b": (1.1e9, 1.3e9),
        "qwen2-7b": (7.4e9, 7.8e9),
        "mistral-nemo-12b": (11.9e9, 12.5e9),
        "hymba-1.5b": (1.4e9, 1.8e9),
        "mamba2-130m": (0.12e9, 0.14e9),
        "whisper-small": (0.22e9, 0.26e9),
        "kimi-k2-1t-a32b": (0.95e12, 1.1e12),
        "qwen3-moe-30b-a3b": (29e9, 32e9),
        "internvl2-2b": (1.7e9, 2.2e9),
    }
    for arch, (lo, hi) in expect.items():
        n = registry.get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)
    # MoE active params
    assert 30e9 <= registry.get_config("kimi-k2-1t-a32b").active_param_count() <= 40e9
    assert 3.0e9 <= registry.get_config("qwen3-moe-30b-a3b").active_param_count() <= 3.7e9
