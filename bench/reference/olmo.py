"""Plain float32 reference of OLMo (arXiv:2402.00838), and its weights.

Straight `jax.numpy` from the published description, with no kernel, cache
or batching: pre-norm decoder layers with OLMo's non-parametric LayerNorm
(no scale, no bias, eps 1e-5), rotary embeddings on queries and keys (NeoX
half rotation, theta from the config), multi-head causal attention, a SwiGLU
MLP, and the output head tied to the input embedding.  Every matrix product
runs at ``Precision.HIGHEST``, so a TPU computes it in float32.

``quant="fp8"`` turns the same function into the correctness control: both
operands of every matrix product, and in training the gradient each product
receives, are rounded to float8 (e4m3, one scale per tensor), the step below
the bfloat16 compute the configuration states.

It imports nothing of the program.  The weights are made here from the seed
and handed to the program, so the program and the reference read the same
numbers and the reference takes nothing the program made.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def leaf_shapes(c: dict) -> dict:
    """Weight name -> shape, stacked over layers, in the program's layout."""
    L, D, F = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    H, KH = c["num_attention_heads"], c["num_key_value_heads"]
    hd = D // H
    return {"embed": (c["vocab_size"], D),
            "attn.wq": (L, D, H, hd), "attn.wk": (L, D, KH, hd),
            "attn.wv": (L, D, KH, hd), "attn.wo": (L, H * hd, D),
            "mlp.wg": (L, D, F), "mlp.wu": (L, D, F), "mlp.wo": (L, F, D)}


def nest(flat: dict) -> dict:
    """Flat ``{"attn.wq": x}`` to the program's parameter tree.  The norms
    are non-parametric, so their entries are empty."""
    layers = {"ln1": {}, "ln2": {}, "attn": {}, "mlp": {}}
    for name, x in flat.items():
        if "." in name:
            group, leaf = name.split(".")
            layers[group][leaf] = x
    return {"embed": flat["embed"], "final_norm": {}, "layers": layers}


def flatten(tree: dict) -> dict:
    out = {"embed": tree["embed"]}
    for group in ("attn", "mlp"):
        for leaf, x in tree["layers"][group].items():
            out[f"{group}.{leaf}"] = x
    return out


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (the driver's exceed 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31),
                              (seed // 2**31) % 2**31)


def init_weights(c: dict, seed: int) -> dict:
    """float32 weights from the seed, made on the device in one jitted call:
    every weight normal with standard deviation ``initializer_range``, as
    the published configuration initialises them."""
    shapes = leaf_shapes(c)
    names = sorted(shapes)
    std = float(c["initializer_range"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        return nest({name: std * jax.random.normal(k, shapes[name],
                                                    jnp.float32)
                     for name, k in zip(names, keys)})

    return make(seed_key(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _qdq(x, quant):
    """Round ``x`` to ``quant`` and back (float32 otherwise untouched)."""
    if quant is None:
        return x
    assert quant == "fp8", quant
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    return _einsum(spec, _qdq(a, "fp8"), _qdq(b, "fp8"))


def _mm_fp8_fwd(spec, a, b):
    qa, qb = _qdq(a, "fp8"), _qdq(b, "fp8")
    return _einsum(spec, qa, qb), (qa, qb)


def _mm_fp8_bwd(spec, res, g):
    """The backward products in float8 too: the incoming gradient is
    rounded like an operand, each with its own scale."""
    _, vjp = jax.vjp(functools.partial(_einsum, spec), *res)
    return vjp(_qdq(g, "fp8"))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(spec, a, b, quant):
    if quant is None:
        return _einsum(spec, a, b)
    assert quant == "fp8", quant
    return _mm_fp8(spec, a, b)


def _ln(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS)


def _rope(x, theta):
    """x: (T, heads, hd); rotates the two halves of each head."""
    T, _, hd = x.shape
    inv = theta ** (-np.arange(hd // 2, dtype=np.float32) / (hd // 2))
    ang = np.arange(T, dtype=np.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c, x, w, quant):
    """One decoder layer over a whole sequence x: (T, D)."""
    T = x.shape[0]
    H, KH = c["num_attention_heads"], c["num_key_value_heads"]
    theta = float(c["rope_theta"])
    h = _ln(x)
    q = _rope(_mm("td,dhk->thk", h, w["attn.wq"], quant), theta)
    k = _rope(_mm("td,dhk->thk", h, w["attn.wk"], quant), theta)
    v = _mm("td,dhk->thk", h, w["attn.wv"], quant)
    k = jnp.repeat(k, H // KH, axis=1)
    v = jnp.repeat(v, H // KH, axis=1)
    s = _mm("thk,shk->hts", q, k, quant) / math.sqrt(q.shape[-1])
    causal = np.tril(np.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("hts,shk->thk", p, v, quant).reshape(T, -1)
    x = x + _mm("tf,fd->td", o, w["attn.wo"], quant)
    h = _ln(x)
    g = _mm("td,df->tf", h, w["mlp.wg"], quant)
    u = _mm("td,df->tf", h, w["mlp.wu"], quant)
    return x + _mm("tf,fd->td", jax.nn.silu(g) * u, w["mlp.wo"], quant)


def hidden(c, flat, tokens, quant=None, remat=False):
    """Final normalised hidden states (T, D) of one token sequence."""
    layer = functools.partial(_layer, c, quant=quant)
    if remat:
        layer = jax.checkpoint(layer)
    per_layer = {k: v for k, v in flat.items() if k != "embed"}

    def body(x, w):
        return layer(x, w), None

    x, _ = jax.lax.scan(body, flat["embed"][tokens], per_layer)
    return _ln(x)


def logits(c, flat, x, quant=None):
    return _mm("td,vd->tv", x, flat["embed"], quant)


# ---------------------------------------------------------------------------
# serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 5))
def _served_gaps(cfg_items, flat, tokens, targets, first, control):
    c = dict(cfg_items)
    n = targets.shape[0]
    with jax.default_matmul_precision("highest"):
        x = jax.lax.dynamic_slice_in_dim(hidden(c, flat, tokens), first, n)
        ref = logits(c, flat, x)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, targets[:, None], axis=-1)[:, 0]
        gaps = best - got
        if not control:
            return gaps, gaps
        xq = jax.lax.dynamic_slice_in_dim(
            hidden(c, flat, tokens, "fp8"), first, n)
        pick = jnp.argmax(logits(c, flat, xq, "fp8"), axis=-1)
        ctrl = best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        return gaps, ctrl


def served_gaps(c: dict, weights: dict, prompt: np.ndarray,
                served: np.ndarray, width: int, n_out: int,
                control: bool = False):
    """Gaps, in logits, by which each served token lies below the
    reference's best token at its position, given the request's own
    ``prompt`` (from position 0, unpadded) and the served tokens before it.
    ``width`` and ``n_out`` (at least the longest prompt plus answer, and
    the longest answer) fix the shapes of every call, so one compile serves
    all requests (positions past the sequence do not reach earlier ones
    under the causal mask).  With ``control`` also returns, per position,
    the gap of the token that the float8 reference puts first."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    first = len(prompt) - 1                 # position of the first answer
    assert len(served) <= n_out and first + n_out <= width, \
        (len(prompt), len(served), width, n_out)
    tokens = np.zeros(width, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(n_out, np.int32)
    targets[:len(served)] = served
    items = tuple(sorted((k, v) for k, v in c.items()
                         if isinstance(v, (int, float, str))))
    gaps, ctrl = _served_gaps(items, flatten(weights), jnp.asarray(tokens),
                              jnp.asarray(targets), first, control)
    return (np.asarray(gaps)[:len(served)], np.asarray(ctrl)[:len(served)])


# ---------------------------------------------------------------------------
# training: loss, clipped gradient and Adam, step by step
# ---------------------------------------------------------------------------

def _xent(c, flat, tokens, labels, quant):
    lg = logits(c, flat, hidden(c, flat, tokens, quant, remat=True), quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(lg, labels[:, None], -1)[:, 0])


@functools.partial(jax.jit, static_argnums=(0, 4))
def _loss_and_grad(cfg_items, flat, tokens, labels, quant):
    """Mean token cross-entropy of a (B, T) batch and its gradient, one
    sequence at a time."""
    c = dict(cfg_items)
    B = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        vg = jax.value_and_grad(lambda w, t, l: _xent(c, w, t, l, quant))

        def body(acc, xs):
            loss, g = vg(flat, *xs)
            return jax.tree.map(lambda a, b: a + b / B, acc,
                                (loss, g)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, flat))
        (loss, grads), _ = jax.lax.scan(body, zero, (tokens, labels))
    return loss, grads


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 3, 4))
def _adam(opt_items, flat, grads, m, v, step):
    """Clip the gradient to the configured global norm, then one Adam step
    with linear warm-up.  Returns (weights, m, v, clipped gradient)."""
    o = dict(opt_items)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(norm, 1e-9))
    grads = {k: g * scale for k, g in grads.items()}
    lr = o["lr"] * jnp.minimum(1.0, (step + 1) / max(o["warmup_steps"], 1))
    t = step + 1.0
    c1, c2 = 1.0 - o["b1"] ** t, 1.0 - o["b2"] ** t
    new_w, new_m, new_v = {}, {}, {}
    for k in flat:
        new_m[k] = o["b1"] * m[k] + (1 - o["b1"]) * grads[k]
        new_v[k] = o["b2"] * v[k] + (1 - o["b2"]) * jnp.square(grads[k])
        upd = (new_m[k] / c1) / (jnp.sqrt(new_v[k] / c2) + o["eps"])
        new_w[k] = flat[k] * (1.0 - o["weight_decay"] * lr) - lr * upd
    return new_w, new_m, new_v, grads


def leaf_norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
            for k, x in tree.items()}


def train_steps(c: dict, opt: dict, weights: dict, batches, quant=None):
    """Run ``len(batches)`` training steps from ``weights``.  Each batch is
    ``(tokens, labels)``, both (B, T).  Returns the loss of each step, the
    per-leaf norms of the first (clipped) gradient and of the change of each
    weight over all steps."""
    items = tuple(sorted((k, v) for k, v in c.items()
                         if isinstance(v, (int, float, str))))
    opt_items = tuple(sorted(opt.items()))
    w = {k: jnp.array(x, jnp.float32) for k, x in flatten(weights).items()}
    w0 = {k: np.asarray(x) for k, x in w.items()}
    m = {k: jnp.zeros_like(x) for k, x in w.items()}
    v = {k: jnp.zeros_like(x) for k, x in w.items()}
    losses, g_first = [], None
    for step, (tokens, labels) in enumerate(batches):
        loss, grads = _loss_and_grad(items, w, jnp.asarray(tokens),
                                     jnp.asarray(labels), quant)
        losses.append(float(loss))
        w, m, v, clipped = _adam(opt_items, w, grads, m, v,
                                 jnp.float32(step))
        if g_first is None:
            g_first = leaf_norms(clipped)
        del grads, clipped
    change = {k: float(np.linalg.norm(
        (np.asarray(w[k], np.float64) - w0[k]).ravel())) for k in w}
    return losses, g_first, change
