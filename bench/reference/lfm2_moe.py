"""Plain float32 reference of LFM2-MoE (LiquidAI LFM2-8B-A1B), and its
weights, for one chip's share of an expert-parallel deployment.

Straight `jax.numpy` from the published description, with no kernel, cache
or batching.  Each of the ``layer_types`` layers is
``h = x + op(RMSNorm(x)); out = h + ffn(RMSNorm(h))`` (eps ``norm_eps``):

- ``conv``: ``in_proj`` D -> 3D split into B, C and x, then
  ``out_proj(C * causal_depthwise_conv(B * x))`` with ``conv_L_cache`` taps,
  no bias and no activation;
- ``full_attention``: GQA with RMSNorm over each query and key head before
  RoPE (NeoX half rotation, ``rope_theta``), causal softmax, ``out_proj``;
- the first ``num_dense_layers`` FFNs are SwiGLU of ``intermediate_size``;
  the others route over ``published.num_experts`` experts: scores
  ``sigmoid(x @ router)``, the experts ``top-k(scores + expert_bias)``, the
  gates the chosen scores (without the bias) normalised to sum 1 and times
  ``routed_scaling_factor``; each expert a SwiGLU of
  ``moe_intermediate_size``.

This chip holds experts ``[0, num_experts)`` of each MoE layer (the config
file's ``num_experts`` is the held count).  The router keeps its published
width and top-k; only the held experts' part of each token's result is
added, and that partial result goes on to the next layer, as on the chip.

Departures from the published model, all noted in the config file:

- the weights are random from the seed (normal, std ``initializer_range``),
  stored as the config file's ``param_dtype`` and upcast here one layer at
  a time; ``expert_bias`` (a buffer the published model learns by
  auxiliary-loss-free balancing) is drawn too, normal with std
  ``EXPERT_BIAS_STD``, so that the choice differs from ``top-k(scores)``;
- each RMSNorm's scale is stored less one (the scale is ``1 + w``, zero
  stored for the published initial scale of 1), as the program stores it;
- the gates are divided by their sum alone, with no guard term;
- the output head is tied to the input embedding (``tie_word_embeddings``).

Every matrix product runs at ``Precision.HIGHEST``.  ``quant="fp8"`` turns
the same function into the correctness control: both operands of every
matrix product, and in training the gradient each receives, are rounded to
float8 (e4m3, one scale per tensor), the step below the bfloat16 compute
the configuration states.  It imports nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EXPERT_BIAS_STD = 0.1


# ---------------------------------------------------------------------------
# the layer schedule and the weights
# ---------------------------------------------------------------------------

def runs(c: dict) -> list:
    """The layers as runs of one kind, ``(mixer, ffn, count)``: a new run
    starts wherever the mixer or the FFN changes.  The program stacks each
    run's weights, so its parameter tree is a list of runs."""
    out = []
    for i, t in enumerate(c["layer_types"]):
        kind = ("attention" if t == "full_attention" else t,
                "mlp" if i < c["num_dense_layers"] else "moe")
        if out and out[-1][:2] == kind:
            out[-1] = kind + (out[-1][2] + 1,)
        else:
            out.append(kind + (1,))
    return out


def _run_shapes(c: dict, mixer: str, ffn: str, n: int) -> dict:
    D = c["hidden_size"]
    H, KH = c["num_attention_heads"], c["num_key_value_heads"]
    hd = D // H
    E, F = c["num_experts"], c["moe_intermediate_size"]
    out = {"ln1.w": (n, D), "ln2.w": (n, D)}
    if mixer == "attention":
        out.update({"attn.wq": (n, D, H, hd), "attn.wk": (n, D, KH, hd),
                    "attn.wv": (n, D, KH, hd), "attn.wo": (n, H * hd, D),
                    "attn.q_norm": (n, hd), "attn.k_norm": (n, hd)})
    else:
        out.update({"conv.in_proj": (n, D, 3 * D),
                    "conv.conv_w": (n, c["conv_L_cache"], D),
                    "conv.out_proj": (n, D, D)})
    if ffn == "mlp":
        Fd = c["intermediate_size"]
        out.update({"mlp.wg": (n, D, Fd), "mlp.wu": (n, D, Fd),
                    "mlp.wo": (n, Fd, D)})
    else:
        out.update({"moe.router": (n, D, c["published"]["num_experts"]),
                    "moe.expert_bias": (n, c["published"]["num_experts"]),
                    "moe.wg": (n, E, D, F), "moe.wu": (n, E, D, F),
                    "moe.wo": (n, E, F, D)})
    return out


def leaf_shapes(c: dict) -> dict:
    """Flat weight name -> shape, in ``flatten``'s names."""
    shapes = {"embed": (c["vocab_size"], c["hidden_size"]),
              "final_norm.w": (c["hidden_size"],)}
    for r, (mixer, ffn, n) in enumerate(runs(c)):
        shapes.update({f"layers.{r}.{k}": s for k, s in
                       _run_shapes(c, mixer, ffn, n).items()})
    return shapes


def flatten(tree: dict) -> dict:
    """The program's parameter tree (or one of its shape) as
    ``{"layers.<run>.<group>.<leaf>": array}``."""
    flat = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path)] = x
    return flat


def nest(flat: dict) -> dict:
    """``flatten``'s inverse: runs become a list."""
    tree: dict = {}
    for name, x in flat.items():
        node = tree
        *head, leaf = name.split(".")
        for k in head:
            node = node.setdefault(k, {})
        node[leaf] = x
    tree["layers"] = [tree["layers"][str(r)]
                      for r in range(len(tree["layers"]))]
    return tree


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (the driver's exceed 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31),
                              (seed // 2**31) % 2**31)


def _std(name: str, std: float) -> float:
    """Norm scales are stored less one (0 for the initial scale of 1)."""
    if name.endswith(("ln1.w", "ln2.w", "final_norm.w", "q_norm",
                      "k_norm")):
        return 0.0
    return EXPERT_BIAS_STD if name.endswith("expert_bias") else std


def init_weights(c: dict, seed: int) -> dict:
    """Weights from the seed, made on the device in one jitted call in the
    config file's ``param_dtype`` (``expert_bias`` stays float32, as the
    program keeps it): normal with std ``initializer_range``, norms at
    their initial scale, the expert bias as the module says."""
    shapes = leaf_shapes(c)
    names = sorted(shapes)
    std = float(c["initializer_range"])
    dtype = jnp.dtype(c["param_dtype"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for name, k in zip(names, keys):
            x = _std(name, std) * jax.random.normal(k, shapes[name],
                                                    jnp.float32)
            out[name] = x if name.endswith("expert_bias") else x.astype(dtype)
        return nest(out)

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


def _rms(c, x, w):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + c["norm_eps"]) * (1.0 + w)


def _rope(x, theta):
    """x: (T, heads, hd); rotates the two halves of each head."""
    T, _, hd = x.shape
    inv = theta ** (-np.arange(hd // 2, dtype=np.float32) / (hd // 2))
    ang = np.arange(T, dtype=np.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(c, h, w, quant):
    T = h.shape[0]
    H, KH = c["num_attention_heads"], c["num_key_value_heads"]
    theta = float(c["rope_theta"])
    q = _rms(c, _mm("td,dhk->thk", h, w["wq"], quant), w["q_norm"])
    k = _rms(c, _mm("td,dhk->thk", h, w["wk"], quant), w["k_norm"])
    q, k = _rope(q, theta), _rope(k, theta)
    v = _mm("td,dhk->thk", h, w["wv"], quant)
    k = jnp.repeat(k, H // KH, axis=1)
    v = jnp.repeat(v, H // KH, axis=1)
    s = _mm("thk,shk->hts", q, k, quant) / math.sqrt(q.shape[-1])
    causal = np.tril(np.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("hts,shk->thk", p, v, quant).reshape(T, -1)
    return _mm("tf,fd->td", o, w["wo"], quant)


def _conv(c, h, w, quant):
    T, D = h.shape
    L = c["conv_L_cache"]
    b, cc, x = jnp.split(_mm("td,de->te", h, w["in_proj"], quant), 3, -1)
    bx = jnp.concatenate([jnp.zeros((L - 1, D), h.dtype), b * x])
    conv = sum(bx[i:i + T] * w["conv_w"][i] for i in range(L))
    return _mm("td,de->te", cc * conv, w["out_proj"], quant)


def _swiglu(spec_in, spec_out, h, wg, wu, wo, quant):
    g = _mm(spec_in, h, wg, quant)
    return _mm(spec_out, jax.nn.silu(g) * _mm(spec_in, h, wu, quant), wo,
               quant)


def route(c, h, w, quant=None):
    """Gates (T, held) of the held experts: each token's chosen scores
    (without the bias), normalised over its top-k, where a held expert was
    chosen, else 0."""
    scores = jax.nn.sigmoid(_mm("td,de->te", h, w["router"], quant))
    _, pick = jax.lax.top_k(scores + w["expert_bias"], c["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, pick, axis=-1)
    gates = chosen / jnp.sum(chosen, -1, keepdims=True) * float(
        c["routed_scaling_factor"])
    held = jnp.arange(c["num_experts"])
    return jnp.sum(jnp.where(pick[:, :, None] == held, gates[:, :, None],
                             0.0), axis=1)


def _moe(c, h, w, quant):
    gates = route(c, h, w, quant)                            # (T, held)
    y = _swiglu("td,edf->etf", "etf,efd->etd", h, w["wg"], w["wu"],
                w["wo"], quant)
    return jnp.einsum("etd,te->td", y, gates, precision=HIGHEST)


def _layer(c, mixer, ffn, x, w, quant):
    """One layer over a whole sequence x: (T, D), its weights upcast."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    g = {}
    for k, v in w.items():
        group, leaf = k.split(".")
        g.setdefault(group, {})[leaf] = v
    h = _rms(c, x, g["ln1"]["w"])
    x = x + (_attention(c, h, g["attn"], quant) if mixer == "attention"
             else _conv(c, h, g["conv"], quant))
    h = _rms(c, x, g["ln2"]["w"])
    if ffn == "mlp":
        m = g["mlp"]
        return x + _swiglu("td,df->tf", "tf,fd->td", h, m["wg"], m["wu"],
                           m["wo"], quant)
    return x + _moe(c, h, g["moe"], quant)


def hidden(c, flat, tokens, quant=None, remat=False):
    """Final normalised hidden states (T, D) of one token sequence; each
    run's layers are scanned, so one layer's weights are upcast at a
    time."""
    x = flat["embed"][tokens].astype(jnp.float32)
    for r, (mixer, ffn, _) in enumerate(runs(c)):
        layer = functools.partial(_layer, c, mixer, ffn, quant=quant)
        if remat:
            layer = jax.checkpoint(layer)
        pre = f"layers.{r}."
        ws = {k[len(pre):]: v for k, v in flat.items() if k.startswith(pre)}
        x, _ = jax.lax.scan(lambda x, w, layer=layer: (layer(x, w), None),
                            x, ws)
    return _rms(c, x, flat["final_norm.w"].astype(jnp.float32))


def logits(c, flat, x, quant=None):
    return _mm("td,vd->tv", x, flat["embed"].astype(jnp.float32), quant)


def _items(c: dict) -> tuple:
    """The config's hashable keys, for a jit's static argument."""
    out = []
    for k, v in sorted(c.items()):
        if isinstance(v, (int, float, str)):
            out.append((k, v))
        elif k == "layer_types":
            out.append((k, tuple(v)))
        elif k == "published":
            out.append((k, tuple(sorted(v.items()))))
    return tuple(out)


def _config(items) -> dict:
    c = dict(items)
    c["published"] = dict(c["published"])
    return c


# ---------------------------------------------------------------------------
# serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 5))
def _served_gaps(cfg_items, flat, tokens, targets, first, control):
    c = _config(cfg_items)
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
    ``width`` and ``n_out`` fix the shapes of every call, so one compile
    serves all requests (positions past the sequence do not reach earlier
    ones: attention is causal and the convolutions look back only).  With
    ``control`` also returns, per position, the gap of the token that the
    float8 reference puts first."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    first = len(prompt) - 1                 # position of the first answer
    assert len(served) <= n_out and first + n_out <= width, \
        (len(prompt), len(served), width, n_out)
    tokens = np.zeros(width, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(n_out, np.int32)
    targets[:len(served)] = served
    gaps, ctrl = _served_gaps(_items(c), flatten(weights),
                              jnp.asarray(tokens), jnp.asarray(targets),
                              first, control)
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
    c = _config(cfg_items)
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
    weight over all steps.  The expert bias takes no gradient: it steers
    the choice of experts only."""
    items = _items(c)
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
