"""Mamba-2 SSD (state-space duality) block, and LFM2's gated short
convolution (``short_conv``, at the end of this file).

Three implementations of the same layer:
  * ``ssd_forward``   — chunked matmul form (training / prefill).  Intra-chunk
    work is attention-like matmuls (MXU-friendly); inter-chunk state passing is
    a ``jax.lax.associative_scan`` so a sequence-sharded (context-parallel)
    layout lowers to a log-depth collective chain instead of a serial loop.
  * ``ssd_step``      — O(1) recurrent decode step.
  * ``ssd_reference`` — naive sequential recurrence (test oracle).

Layout: d_inner = expand*d_model, H heads of P = head_dim, state N, one B/C
group (mamba2 default n_groups=1).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, SSMConfig


def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = s.num_heads or (d_inner // s.head_dim)
    return d_inner, nheads, s.head_dim, s.state_dim


def ssd_init(cfg: ModelConfig, key, stacked: Optional[int] = None):
    s = cfg.ssm
    d = cfg.d_model
    DI, H, P, N = ssm_dims(cfg)
    conv_dim = DI + 2 * N
    ks = jax.random.split(key, 4)
    L = () if stacked is None else (stacked,)

    def mk(k, din, dout):
        return (jax.random.truncated_normal(k, -2.0, 2.0, L + (din, dout),
                                            jnp.float32) / np.sqrt(din))
    # dt_bias: softplus^-1 of log-spaced dt in [1e-3, 1e-1]
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), H)).astype(np.float32)
    dt_bias = np.log(np.expm1(dt))
    a_init = np.linspace(1.0, 16.0, H).astype(np.float32)
    return {
        "in_proj": mk(ks[0], d, 2 * DI + 2 * N + H),
        "conv_w": (jax.random.truncated_normal(
            ks[1], -2.0, 2.0, L + (s.conv_width, conv_dim), jnp.float32)
            / np.sqrt(s.conv_width)),
        "conv_b": jnp.zeros(L + (conv_dim,), jnp.float32),
        "A_log": jnp.broadcast_to(jnp.log(jnp.asarray(a_init)), L + (H,)),
        "D": jnp.ones(L + (H,), jnp.float32),
        "dt_bias": jnp.broadcast_to(jnp.asarray(dt_bias), L + (H,)),
        "norm_w": jnp.zeros(L + (DI,), jnp.float32),
        "out_proj": mk(ks[3], DI, d),
    }


def _split_proj(cfg: ModelConfig, proj):
    DI, H, P, N = ssm_dims(cfg)
    z, x, B, C, dt = jnp.split(
        proj, [DI, 2 * DI, 2 * DI + N, 2 * DI + 2 * N], axis=-1)
    return z, x, B, C, dt


def _causal_conv(xBC, w, b):
    """Depthwise causal conv.  xBC: (B, T, C); w: (W, C)."""
    W = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC, dtype=jnp.float32)
    for i in range(W):
        out = out + pad[:, i: i + xBC.shape[1], :].astype(jnp.float32) * w[i]
    return jax.nn.silu(out + b).astype(xBC.dtype)


def _gated_rmsnorm(y, z, w, eps=1e-6):
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    return (y * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32)))


def ssd_forward(cfg: ModelConfig, p, u, *, init_state=None,
                dtype=jnp.bfloat16):
    """u: (B, T, D) -> (out (B,T,D), final ssm state (B,H,P,N), conv tail).

    T must be a multiple of the chunk length after internal padding.
    """
    s = cfg.ssm
    DI, H, P, N = ssm_dims(cfg)
    B_, T, _ = u.shape
    Q = min(s.chunk, T)
    if T % Q:
        padT = Q - T % Q
        u = jnp.pad(u, ((0, 0), (0, padT), (0, 0)))
    else:
        padT = 0
    Tp = u.shape[1]
    nc = Tp // Q

    proj = jnp.einsum("btd,de->bte", u, p["in_proj"].astype(dtype))
    z, x, Bv, Cv, dt = _split_proj(cfg, proj)
    xBC = jnp.concatenate([x, Bv, Cv], axis=-1)
    # raw (pre-conv) tail of the true sequence — the decode conv history
    w1 = s.conv_width - 1
    raw_tail = xBC[:, max(0, T - w1): T, :].astype(jnp.bfloat16)
    if T < w1:
        raw_tail = jnp.pad(raw_tail, ((0, 0), (w1 - T, 0), (0, 0)))
    xBC = _causal_conv(xBC, p["conv_w"], p["conv_b"])
    x, Bv, Cv = jnp.split(xBC, [DI, DI + N], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])    # (B,Tp,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                   # (H,)
    x = x.reshape(B_, Tp, H, P)

    # mask padding so it contributes nothing and carries no decay
    if padT:
        valid = (jnp.arange(Tp) < T)[None, :, None]
        dt = jnp.where(valid, dt, 0.0)

    a = dt * A                                                     # (B,Tp,H) <=0
    ac = a.reshape(B_, nc, Q, H)
    cum = jnp.cumsum(ac, axis=2)                                   # (B,nc,Q,H)
    # intra-chunk: L[i,j] = exp(cum_i - cum_j), i >= j
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]            # (B,nc,Q,Q,H)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)
    Bc = Bv.reshape(B_, nc, Q, N)
    Cc = Cv.reshape(B_, nc, Q, N)
    xc = x.reshape(B_, nc, Q, H, P)
    dtc = dt.reshape(B_, nc, Q, H)

    CB = jnp.einsum("bcin,bcjn->bcij", Cc.astype(jnp.bfloat16),
                    Bc.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    M = CB[..., None] * L                                          # (B,nc,Q,Q,H)
    xdt = xc.astype(jnp.float32) * dtc[..., None]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", M.astype(jnp.bfloat16),
                         xdt.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)

    # chunk-final states
    decay_end = jnp.exp(cum[:, :, -1:, :] - cum)                   # (B,nc,Q,H)
    S = jnp.einsum("bcjn,bcjh,bcjhp->bchpn", Bc.astype(jnp.float32),
                   decay_end * dtc, xc.astype(jnp.float32))        # (B,nc,H,P,N)
    chunk_decay = jnp.exp(cum[:, :, -1, :])                        # (B,nc,H)

    def combine(e1, e2):
        d1, s1 = e1
        d2, s2 = e2
        return d1 * d2, s1 * d2[..., None, None] + s2

    dA, Sacc = jax.lax.associative_scan(combine, (chunk_decay, S), axis=1)
    # state *before* chunk c (exclusive scan) + init contribution
    before = jnp.concatenate(
        [jnp.zeros_like(Sacc[:, :1]), Sacc[:, :-1]], axis=1)       # (B,nc,H,P,N)
    decay_excl = jnp.concatenate(
        [jnp.ones_like(dA[:, :1]), dA[:, :-1]], axis=1)            # (B,nc,H)
    if init_state is not None:
        before = before + (init_state[:, None].astype(jnp.float32)
                           * decay_excl[..., None, None])
        final_state = (Sacc[:, -1]
                       + init_state.astype(jnp.float32) * dA[:, -1][..., None, None])
    else:
        final_state = Sacc[:, -1]

    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp", Cc.astype(jnp.float32),
                         before, jnp.exp(cum))
    y = (y_intra + y_inter).reshape(B_, Tp, H, P)
    y = y + p["D"].astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    y = y.reshape(B_, Tp, DI)
    y = _gated_rmsnorm(y, z, p["norm_w"])
    out = jnp.einsum("bte,ed->btd", y.astype(dtype), p["out_proj"].astype(dtype))
    if padT:
        out = out[:, :T]
    return out, final_state.astype(jnp.float32), raw_tail


def ssd_step(cfg: ModelConfig, p, u_t, state, conv_state, *,
             dtype=jnp.bfloat16):
    """Single decode step.

    u_t: (B, D); state: (B, H, P, N); conv_state: (B, W-1, conv_dim) raw
    (pre-activation) xBC history.  Returns (out (B,D), state, conv_state).
    """
    s = cfg.ssm
    DI, H, P, N = ssm_dims(cfg)
    proj = jnp.einsum("bd,de->be", u_t, p["in_proj"].astype(dtype))
    z, x, Bv, Cv, dt = _split_proj(cfg, proj)
    xBC = jnp.concatenate([x, Bv, Cv], axis=-1)                    # (B, conv_dim)
    hist = jnp.concatenate([conv_state, xBC[:, None, :]], axis=1)  # (B, W, conv)
    conv_out = jnp.einsum("bwc,wc->bc", hist.astype(jnp.float32), p["conv_w"])
    conv_out = jax.nn.silu(conv_out + p["conv_b"]).astype(dtype)
    new_conv_state = hist[:, 1:, :]
    x, Bv, Cv = jnp.split(conv_out, [DI, DI + N], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])    # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    x = x.reshape(-1, H, P).astype(jnp.float32)
    da = jnp.exp(dt * A)                                           # (B,H)
    state = (state * da[..., None, None]
             + jnp.einsum("bn,bh,bhp->bhpn", Bv.astype(jnp.float32),
                          dt, x))
    y = jnp.einsum("bn,bhpn->bhp", Cv.astype(jnp.float32), state)
    y = y + p["D"][None, :, None] * x
    y = y.reshape(-1, DI)
    y = _gated_rmsnorm(y, z, p["norm_w"])
    out = jnp.einsum("be,ed->bd", y.astype(dtype), p["out_proj"].astype(dtype))
    return out, state, new_conv_state


def init_ssm_state(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    DI, H, P, N = ssm_dims(cfg)
    return (jnp.zeros((batch, H, P, N), jnp.float32),
            jnp.zeros((batch, s.conv_width - 1, DI + 2 * N), jnp.bfloat16))


def ssd_reference(cfg: ModelConfig, p, u, *, init_state=None):
    """Naive sequential recurrence — the oracle for ssd_forward/ssd_step."""
    s = cfg.ssm
    DI, H, P, N = ssm_dims(cfg)
    B_, T, _ = u.shape
    proj = jnp.einsum("btd,de->bte", u.astype(jnp.float32), p["in_proj"])
    z, x, Bv, Cv, dt = _split_proj(cfg, proj)
    xBC = jnp.concatenate([x, Bv, Cv], axis=-1)
    xBC = _causal_conv(xBC, p["conv_w"], p["conv_b"]).astype(jnp.float32)
    x, Bv, Cv = jnp.split(xBC, [DI, DI + N], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    x = x.reshape(B_, T, H, P)
    state = (jnp.zeros((B_, H, P, N), jnp.float32) if init_state is None
             else init_state.astype(jnp.float32))

    def step(state, xs):
        xt, bt, ct, dtt = xs
        da = jnp.exp(dtt * A)                                      # (B,H)
        state = (state * da[..., None, None]
                 + jnp.einsum("bn,bh,bhp->bhpn", bt, dtt, xt))
        y = jnp.einsum("bn,bhpn->bhp", ct, state)
        return state, y

    xs = (x.transpose(1, 0, 2, 3), Bv.transpose(1, 0, 2),
          Cv.transpose(1, 0, 2), dt.transpose(1, 0, 2))
    state, ys = jax.lax.scan(step, state, xs)
    y = ys.transpose(1, 0, 2, 3) + p["D"][None, None, :, None] * x
    y = y.reshape(B_, T, DI)
    y = _gated_rmsnorm(y, z, p["norm_w"])
    out = jnp.einsum("bte,ed->btd", y, p["out_proj"].astype(jnp.float32))
    return out, state


# ---------------------------------------------------------------------------
# Gated short convolution (lfm2 "conv" layers)
# ---------------------------------------------------------------------------
# in_proj D -> 3D is split into B, C and x; the block computes
# ``out_proj(C * causal_depthwise_conv(B * x))`` with a kernel of
# ``cfg.conv_width`` taps, no bias and no activation.  Its serving state is
# the last ``conv_width - 1`` rows of B * x (bf16, as the products are
# computed), so a sequence split across dispatches or decoded a token at a
# time sees exactly the rows a single pass would.


def short_conv_init(cfg: ModelConfig, key, stacked: Optional[int] = None):
    d, W = cfg.d_model, cfg.conv_width
    ks = jax.random.split(key, 3)
    L = () if stacked is None else (stacked,)

    def mk(k, *dims):
        return (jax.random.truncated_normal(k, -2.0, 2.0, L + dims,
                                            jnp.float32) / np.sqrt(dims[0]))
    return {"in_proj": mk(ks[0], d, 3 * d), "conv_w": mk(ks[1], W, d),
            "out_proj": mk(ks[2], d, d)}


def short_conv(cfg: ModelConfig, p, x, state, valid, *, dtype=jnp.bfloat16):
    """x (B, T, D) continuing each row from ``state`` (B, W-1, D), the B*x
    rows before it; ``valid`` (B,) int32 counts each row's real tokens (the
    rest of the row is padding).  Returns (out (B, T, D), the state after
    the row's ``valid`` tokens — unchanged where ``valid`` is 0)."""
    W = cfg.conv_width
    T = x.shape[1]
    proj = jnp.einsum("btd,de->bte", x, p["in_proj"].astype(dtype))
    b, c, xx = jnp.split(proj, 3, axis=-1)
    hist = jnp.concatenate([state.astype(dtype), b * xx], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    conv = hist[:, 0:T].astype(jnp.float32) * w[0]
    for i in range(1, W):
        conv = conv + hist[:, i:i + T].astype(jnp.float32) * w[i]
    y = (c.astype(jnp.float32) * conv).astype(dtype)
    out = jnp.einsum("btd,de->bte", y, p["out_proj"].astype(dtype))
    new_state = jax.vmap(lambda h, n: jax.lax.dynamic_slice_in_dim(
        h, n, W - 1, axis=0))(hist, valid.astype(jnp.int32))
    return out, new_state
