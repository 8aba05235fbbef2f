"""Batched serving engine: incremental continuous batching + chunked decode.

Production-shaped but container-sized: requests arrive with prompts, get
batched into fixed-size decode slots (static shapes for jit), and a decode
loop advances all active slots, retiring finished requests and admitting
queued ones.

The fast path (every transformer-cache family):
  * **Incremental admission** — admitting a request prefills ONLY its slot
    (``api.prefill_slot``: a batch-1 prefill whose KV/state rows are written
    into the live batch cache), so admitting request k+1 never recomputes
    request k.  Per-slot valid lengths live in a device-resident ``seq_lens``
    vector instead of the cache's shared scalar position.
  * **Paged decode attention** — each step gathers only a slot's valid cache
    prefix (``kernels/decode_attention``: Pallas paged kernel on TPU, dense
    XLA reference elsewhere) instead of scanning the full ``max_len`` dense
    cache.
  * **Multi-step on-device decode** — ``api.decode_n`` scans ``chunk`` steps
    per dispatch with on-device argmax/sampling and per-slot done-masking,
    so the device→host sync happens once per chunk, not once per token.
    Chunking is numerics-neutral: greedy outputs are bitwise identical for
    any chunk size (the property benchmarks/cluster_session.py pins) for
    every family whose per-token compute is batch-lane independent.  The
    one caveat is the capacity coupling of a *dropping* MoE layer:
    admission lands on chunk boundaries, so chunk size can shift WHEN a
    freed slot's lane flips from a frozen repeat-token to a fresh request,
    and a saturated expert's token-drop choice sees those lane contents
    (identical admission schedules — e.g. uniform budgets — are still
    bitwise stable).  The held-expert layer of the pooled path
    (`moe.moe_held`) drops nothing, so it has no such coupling.

Batching discipline: one batch-1 prefill program + one chunked decode
program, both jit'd once — the static-shape serving pattern TPU serving
stacks use.  The whisper enc-dec family keeps the legacy full-batch
prefill + per-token loop (its cache layout has no per-slot insert yet).

A model with short-convolution layers (a per-layer mixer schedule, lfm2)
keeps two kinds of state in one manager: the block pool holds KV for its
attention layers, and each slot carries its conv layers' state beside the
pool — reset when a prompt is admitted, written by each prefill dispatch,
advanced by every decode step.  It serves on the pooled layout with
``kv_share=False`` only: a shared prefix's blocks carry no conv state, and
the dense per-slot layout has none at all.  Its held-expert load per
decode step comes back with the chunk's tokens and is counted in
``serve.moe_pairs`` / ``serve.moe_experts_touched``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.costmodel import TPU_V4
from repro.models import api
from repro.models import quant as QUANT
from repro.models import transformer as TF
from repro.obs import Telemetry
from repro.parallel.context import LOCAL, ParallelContext, activate
from repro.serve.kvpool import KVPool


@dataclasses.dataclass(frozen=True)
class SliceSpec:
    """Serving-session shape: the static-compile envelope of one engine.

    One value object instead of loose ``slots/max_len/prompt_len`` kwargs so
    slice handles (`repro.cluster`) can pass serving configuration around,
    hash it, and log it.

    ``chunk`` is the serve fast-path knob: decode tokens advanced per device
    dispatch (1 = legacy per-token host loop, same numerics).

    ``kv_block > 0`` switches the engine to the POOLED prefix-shared KV
    cache (`serve/kvpool.py`): per-slot cache rows become indirection tables
    over a shared block pool, admissions sharing a prompt prefix reuse
    already-prefilled blocks, and prefill runs as ``suffix_len``-token
    dispatches over only the unshared suffix, one admitted request per row
    (the row count follows the weights' bytes: `admission_rows`).
    ``kv_share=False`` keeps the pooled layout but never matches/publishes —
    the bitwise-identity baseline arm.  ``kv_blocks`` sizes the pool
    (0 = 2x the table capacity, so published prefixes survive slot churn).
    """
    slots: int = 4                  # decode batch width (static shape)
    max_len: int = 256              # KV-cache length per slot
    prompt_len: int = 32            # padded prefill length
    greedy: bool = True
    chunk: int = 8                  # decode steps per dispatch
    kv_block: int = 0               # pooled KV block size (0 = dense cache)
    kv_share: bool = True           # match/publish prompt prefixes
    kv_blocks: int = 0              # pool size (0 = 2 * slots * table width)
    suffix_len: int = 0             # suffix-prefill tokens per row and
                                    # dispatch (0 = prompt_len)
    quant: str = "none"             # weight storage: "none" | "int8"
                                    # (models/quant.py tile-wise int8; the
                                    # engine quantises its params at init)

    def __post_init__(self):
        assert self.slots >= 1 and 0 < self.prompt_len <= self.max_len, self
        assert self.chunk >= 1, self
        assert self.quant in ("none", "int8"), self
        if self.kv_block:
            assert self.max_len % self.kv_block == 0, \
                f"max_len {self.max_len} not a multiple of kv_block " \
                f"{self.kv_block}"
            assert self.suffix_len >= 0 and self.kv_blocks >= 0, self


@dataclasses.dataclass(eq=False)
class Request:
    """One serving request.  ``eq=False`` keeps identity semantics: a
    generated ``__eq__`` would compare ``np.ndarray`` prompts elementwise,
    so membership tests (``r in engine.active``) could raise on value-equal
    requests."""
    rid: int
    prompt: np.ndarray              # (prompt_len,) int32
    max_new_tokens: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None


# tokens of one dispatch per byte of a stored weight at which it stops
# being bound by its weight stream: peak FLOP/s over HBM bytes/s, over the
# 2 FLOPs a weight costs per token (one fixed preset, TPU v4's)
_RIDGE_PER_WEIGHT_BYTE = TPU_V4.peak_flops_bf16 / TPU_V4.hbm_bw / 2


def admission_rows(slots: int, suffix_len: int, weight_bytes: float) -> int:
    """Rows of one pooled admission dispatch: as many ``suffix_len``-token
    rows as it takes to reach the ridge (`_RIDGE_PER_WEIGHT_BYTE` times the
    bytes of a stored weight), at most ``slots``.  A
    ``suffix_len`` of 512 gives 1 for weights of up to 4 bytes, so a wave
    of fewer requests than slots does not pay for the empty rows.  The
    R > 1 side (short rows, where a further row is assumed to cost
    nothing as the weights are read anyway) has not been measured on a
    chip: no benchmark cell runs it, only small test engines."""
    ridge = _RIDGE_PER_WEIGHT_BYTE * weight_bytes
    return max(1, min(slots, math.ceil(ridge / suffix_len)))


def _pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


@functools.lru_cache(maxsize=32)
def _fast_programs(cfg: ModelConfig, spec: SliceSpec, ctx: ParallelContext):
    """The jit'd admission + chunked-decode programs for one serving shape.

    Cached on the (frozen, hashable) config triple so every engine with the
    same shape shares ONE compilation — a fleet scale-up brings a replica
    online without recompiling, and N replicas cost one compile, not N.
    ``params``/``cache`` stay call arguments, so the cache never pins model
    weights."""
    sample_key = jax.random.PRNGKey(spec.slots)

    def _admit(params, cache, batch, slots_, rids, seq_lens, last, salt):
        with activate(ctx):
            logits, cache = api.prefill_slot(
                cfg, params, batch, cache, slots_, ctx, max_len=spec.max_len)
        # cached rows include the vision prefix for VLMs — the
        # text-token count alone would mask out valid prompt KV
        prefilled = batch["tokens"].shape[1] + (cfg.vision_prefix or 0)
        # the first token follows decode_n's (salt, position) key scheme;
        # decode positions start at prefilled+1, so the streams never
        # collide
        nxt = TF.sample_tokens(logits, sample_key, rids,
                               jnp.full_like(rids, prefilled),
                               greedy=spec.greedy)
        seq_lens = seq_lens.at[slots_].set(prefilled)
        last = last.at[slots_].set(nxt)
        salt = salt.at[slots_].set(rids)
        return nxt, cache, seq_lens, last, salt

    def _decode(params, cache, tokens, seq_lens, budget, key, salt,
                num_steps):
        with activate(ctx):
            return api.decode_n(
                cfg, params, cache, tokens, seq_lens, budget, ctx,
                num_steps=num_steps, greedy=spec.greedy, key=key,
                salt=salt) + (None,)

    return (jax.jit(_admit, donate_argnums=(1,)),
            jax.jit(_decode, donate_argnums=(1,), static_argnums=(7,)))


@functools.lru_cache(maxsize=32)
def _pooled_programs(cfg: ModelConfig, spec: SliceSpec, ctx: ParallelContext):
    """Jit'd suffix-prefill admission + pooled chunked decode.

    Each admission row prefills ``suffix_len`` tokens of the request in the
    slot ``rows`` names for it (padding rows name slot ``spec.slots``, out
    of range, so their writes drop); the engine picks the row count once
    (`admission_rows`), so this is one compiled program.  A long suffix
    prefills in several chained dispatches, and only rows whose ``commit``
    flag is set (the chunk holding their last prompt token) fold their
    logits into the decode state — everything else is a masked no-op, so
    idle rows and mid-suffix chunks never perturb live slots."""
    sample_key = jax.random.PRNGKey(spec.slots)

    def _admit(params, cache, tokens, start, valid, tables, rows, rids,
               plens, commit, seq_lens, last, salt):
        with activate(ctx):
            logits, cache = api.prefill_suffix(
                cfg, params, cache, tokens, start, valid, tables, ctx,
                slots=rows)
        # the dense fast path's key scheme, but the fold position is the
        # TRUE prompt length (pooled rows are left-aligned, not padded to
        # prompt_len)
        nxt = TF.sample_tokens(logits, sample_key, rids, plens,
                               greedy=spec.greedy)
        at = jnp.where(commit, rows, spec.slots)
        seq_lens = seq_lens.at[at].set(plens, mode="drop")
        last = last.at[at].set(nxt, mode="drop")
        salt = salt.at[at].set(rids, mode="drop")
        return nxt, cache, seq_lens, last, salt

    # a schedule with MoE layers reports its held-expert load per step
    load = bool(cfg.mixers) and cfg.moe is not None

    def _decode(params, cache, tokens, seq_lens, budget, key, salt, tables,
                num_steps):
        with activate(ctx):
            out = api.decode_n(
                cfg, params, cache, tokens, seq_lens, budget, ctx,
                num_steps=num_steps, greedy=spec.greedy, key=key, salt=salt,
                tables=tables, moe_load=load)
        return out if load else out + (None,)

    return (jax.jit(_admit, donate_argnums=(1,)),
            jax.jit(_decode, donate_argnums=(1,), static_argnums=(8,)))


@functools.lru_cache(maxsize=8)
def _legacy_programs(cfg: ModelConfig, spec: SliceSpec,
                     ctx: ParallelContext):
    """Full-batch prefill + per-token decode (whisper enc-dec cache)."""

    def _prefill(params, batch):
        with activate(ctx):
            return api.prefill(cfg, params, batch, ctx, max_len=spec.max_len)

    def _decode(params, cache, tokens):
        with activate(ctx):
            return api.decode_step(cfg, params, cache, tokens, ctx)

    return jax.jit(_prefill), jax.jit(_decode, donate_argnums=(1,))


class ServeEngine:
    """Continuous-batching serving engine (the PR-3 fast path).

    One engine owns `spec.slots` decode slots over a paged KV cache:
    admission prefills ONLY the admitted requests, decode advances all
    slots `spec.chunk` tokens per dispatch with on-device sampling and done-masking, and per-slot valid lengths
    drive the paged decode-attention kernel.  Greedy outputs are bitwise
    chunk-invariant.

    Args:
      cfg: model config (any family except audio rides the fast path).
      params: model parameters pytree.
      spec: `SliceSpec` serving envelope (slots/max_len/prompt_len/chunk).
      ctx: `ParallelContext` for sharded serving and kernel dispatch knobs.
    """

    def __init__(self, cfg: ModelConfig, params,
                 spec: Optional[SliceSpec] = None, *,
                 ctx: ParallelContext = LOCAL,
                 obs: Optional[Telemetry] = None,
                 obs_labels: Optional[Dict[str, Any]] = None):
        spec = spec or SliceSpec()
        self.cfg = cfg
        if spec.quant == "int8":
            params = QUANT.quantize_params(cfg, params)
        self.params = params
        self.spec = spec
        self.slots = spec.slots
        self.max_len = spec.max_len
        self.prompt_len = spec.prompt_len
        self.ctx = ctx
        self.greedy = spec.greedy
        self.queue: List[Request] = []        # every request, for stats
        self.pending: List[Request] = []      # submitted, not yet admitted
        self._next_rid = 0                    # monotonic: queue length would
                                              # recycle rids after an
                                              # export_inflight, colliding
                                              # sampling salts / fleet keys
        self.active: List[Optional[Request]] = [None] * spec.slots
        self.cache = None
        self.last_tokens = jnp.zeros((spec.slots,), jnp.int32)
        self.seq_lens = jnp.zeros((spec.slots,), jnp.int32)
        # per-slot sampling salt = rid of the request occupying the slot,
        # so distinct requests reusing a slot draw decorrelated streams
        self.sample_salt = jnp.zeros((spec.slots,), jnp.int32)
        self.chunk_lat_s: List[float] = []
        self._chunk_ema: Optional[float] = None   # O(1) running latency EMA
        self._steps = 0
        self._sample_key = jax.random.PRNGKey(spec.slots)
        # whisper's enc-dec cache has no per-slot insert; it keeps the
        # legacy full-batch prefill + per-token decode loop
        self._fast = cfg.family != "audio"
        # pooled prefix-shared KV (kvpool.py): dense attention stacks and
        # per-layer mixer schedules
        self._pooled = self._fast and spec.kv_block > 0
        # prefill-cost proxy (dispatch width x batch rows, summed over
        # prefill dispatches) + prefix-sharing counters — the kv-prefix
        # benchmark compares these across pooled/legacy arms.  They live in
        # the metrics registry (labeled, so a shared fleet-wide Telemetry
        # keeps engines apart); the old attribute names stay as property
        # views below.
        self.obs = obs if obs is not None else Telemetry()
        labels = dict(obs_labels or {})
        reg = self.obs.metrics
        self._c_prefill = reg.counter("serve.prefill_flops_proxy", **labels)
        self._c_kv_prompt = reg.counter("serve.kv_prompt_tokens", **labels)
        self._c_kv_shared = reg.counter("serve.kv_shared_tokens", **labels)
        self._c_mig_shared = reg.counter(
            "serve.kv_migrated_shared_blocks", **labels)
        self._c_mig_suffix = reg.counter(
            "serve.kv_migrated_suffix_blocks", **labels)

        if cfg.conv_layers and not self._pooled:
            raise ValueError(
                f"{cfg.name} keeps short-convolution state, which only the "
                "pooled KV layout carries: serve it with kv_block > 0")
        if cfg.conv_layers and spec.kv_share:
            raise ValueError(
                f"{cfg.name} keeps short-convolution state, and a shared "
                "prefix's blocks carry none: serve it with kv_share=False")
        self._c_moe_pairs = reg.counter("serve.moe_pairs", **labels)
        self._c_moe_touched = reg.counter("serve.moe_experts_touched",
                                          **labels)
        self._c_skipped = reg.counter("serve.decode_slots_skipped", **labels)
        if self._pooled:
            if not api.has_pooled_layout(cfg):
                raise NotImplementedError(
                    f"{cfg.name}: no pooled KV layout for the "
                    f"{cfg.family} family")
            nb = spec.max_len // spec.kv_block
            self._nb = nb
            self._suffix_len = spec.suffix_len or spec.prompt_len
            leaves = jax.tree.leaves(params)
            self._rows = admission_rows(
                spec.slots, self._suffix_len,
                QUANT.storage_bytes(params) / sum(x.size for x in leaves))
            self.kvpool = KVPool(
                num_blocks=spec.kv_blocks or 2 * spec.slots * nb,
                block_size=spec.kv_block, slots=spec.slots,
                blocks_per_slot=nb)
            # host mirror of the device tables; OOB sentinel = unadmitted
            # (the bt kernel clamps it; seq_lens=0 masks the compute)
            self._tables_np = np.full((spec.slots, nb),
                                      self.kvpool.num_blocks, np.int32)
            self.tables = jnp.asarray(self._tables_np)
            self._admit_fn, self._decode_fn = _pooled_programs(cfg, spec,
                                                               ctx)
        elif self._fast:
            self._admit_fn, self._decode_fn = _fast_programs(cfg, spec, ctx)
        else:
            self._prefill, self._decode = _legacy_programs(cfg, spec, ctx)

    # -- request lifecycle ----------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32) -> Request:
        """Enqueue one prompt; returns its `Request` handle (admission
        happens on a later `step`/`step_chunk`).  The prompt is truncated
        to the last `spec.prompt_len` tokens at prefill."""
        r = Request(rid=self._next_rid, prompt=np.asarray(prompt, np.int32),
                    max_new_tokens=max_new_tokens,
                    t_submit=time.perf_counter())
        self._next_rid += 1
        self.queue.append(r)
        self.pending.append(r)
        return r

    def _extra_inputs(self, n: int) -> Dict[str, Any]:
        extra: Dict[str, Any] = {}
        if self.cfg.family == "vlm":
            extra["patches"] = jnp.zeros(
                (n, self.cfg.vision_prefix, self.cfg.vision_dim),
                jnp.float32)
        return extra

    def _admit(self) -> bool:
        """Fill empty slots from the queue: the whole admission wave is ONE
        batched prefill dispatch writing only the admitted slots' cache rows
        — active slots are never recomputed.  The wave is padded to a fixed
        width of ``slots`` (static shapes: exactly one compiled admission
        program); padding rows carry an out-of-bounds slot index, so their
        scatter updates are dropped on-device."""
        if not self._fast:
            return self._admit_full()
        if self._pooled:
            return self._admit_pooled()
        if not self.pending:                   # O(1) fast-out per chunk
            return False
        free = [i for i, a in enumerate(self.active)
                if a is None or a.done]
        n = min(len(self.pending), len(free))
        if n == 0:
            return False
        with self.obs.span("serve.admit", requests=n):
            self._admit_dense(free[:n])
        return True

    def _admit_dense(self, free: List[int]) -> None:
        n = len(free)
        with self.obs.span("serve.admit.plan"):
            if self.cache is None:
                self.cache = api.init_cache(self.cfg, self.slots,
                                            self.max_len)
            admitted = self.pending[:n]
            del self.pending[:n]
            # padding rows keep the out-of-bounds sentinel slot
            slots = np.full((self.slots,), self.slots, np.int32)
            slots[:n] = free
            prompts = np.zeros((self.slots, self.prompt_len), np.int32)
            tokens = 0
            for row, (slot, r) in enumerate(zip(slots[:n], admitted)):
                self.active[slot] = r
                seq = r.prompt[-self.prompt_len:]
                prompts[row, -len(seq):] = seq
                tokens += len(seq)
            rids = np.zeros((self.slots,), np.int32)
            rids[:n] = [r.rid for r in admitted]
            self._c_prefill.inc(self.prompt_len * self.slots)
            batch = {"tokens": jnp.asarray(prompts),
                     **self._extra_inputs(self.slots)}
            slots, rids = jnp.asarray(slots), jnp.asarray(rids)
        with self.obs.span("serve.admit.prefill", tokens=tokens,
                           width=self.slots * self.prompt_len):
            nxt, self.cache, self.seq_lens, self.last_tokens, \
                self.sample_salt = self._admit_fn(
                    self.params, self.cache, batch, slots, rids,
                    self.seq_lens, self.last_tokens, self.sample_salt)
        with self.obs.span("serve.admit.sync"):
            nxt = np.asarray(nxt)
        now = time.perf_counter()
        for row, r in enumerate(admitted):
            r.out_tokens.append(int(nxt[row]))
            r.t_first = now
            if len(r.out_tokens) >= r.max_new_tokens:
                r.done = True
                r.t_done = now

    def _admit_pooled(self) -> bool:
        """Pooled admission: map each admitted prompt's shared prefix onto
        already-prefilled pool blocks (kvpool.admit) and prefill ONLY the
        unshared suffix, ``suffix_len`` tokens per dispatch row, one row per
        admitted request — a request whose whole prompt header is cached
        pays one short row, and a one-request wave computes one row, not
        one per slot.  Publication into the prefix trie happens AFTER the
        dispatches land, so two same-wave admissions can never alias blocks
        still being written."""
        if not self.pending:
            return False
        free = [i for i, a in enumerate(self.active)
                if a is None or a.done]
        n = min(len(self.pending), len(free))
        if n == 0:
            return False
        with self.obs.span("serve.admit", requests=n):
            with self.obs.span("serve.admit.plan"):
                rows = self._plan_pooled(free[:n])
            self._prefill_pooled(rows)
        return True

    def _plan_pooled(self, free: List[int]) -> list:
        """Seat the first pending requests in the ``free`` slots, map their
        prompts onto pool blocks and upload the tables; returns
        ``(slot, request, start, seq)`` per admitted request."""
        if self.cache is None:
            self.cache = api.init_kv_pool(
                self.cfg, self.kvpool.num_blocks, self.spec.kv_block,
                slots=self.slots)
        admitted = self.pending[:len(free)]
        del self.pending[:len(free)]
        bs = self.spec.kv_block
        rows = []
        for slot, r in zip(free, admitted):
            self.active[slot] = r
            seq = np.asarray(r.prompt, np.int32)[-self.prompt_len:]
            table, matched = self.kvpool.admit(
                slot, seq, share=self.spec.kv_share)
            self._tables_np[slot] = table
            self._c_kv_prompt.inc(len(seq))
            self._c_kv_shared.inc(matched * bs)
            rows.append((slot, r, matched * bs, seq))
        self.tables = jnp.asarray(self._tables_np)
        return rows

    def _prefill_pooled(self, rows: list) -> None:
        """Prefill the admitted suffixes and hand each request its first
        token.  The wave runs in groups of ``self._rows`` requests, one per
        dispatch row (padding rows name no slot); each group chains
        ``suffix_len``-token dispatches until its longest suffix is in.
        The first tokens are read once, after the wave's last dispatch."""
        R, Tc = self._rows, self._suffix_len
        firsts = []             # (device tokens, group start, commit rows)
        for g in range(0, len(rows), R):
            group = rows[g:g + R]
            slot_of = np.full((R,), self.slots, np.int32)
            tables = np.full((R, self._nb), self.kvpool.num_blocks, np.int32)
            rids = np.zeros((R,), np.int32)
            plens = np.zeros((R,), np.int32)
            for row, (slot, r, _, seq) in enumerate(group):
                slot_of[row], rids[row], plens[row] = slot, r.rid, len(seq)
                tables[row] = self._tables_np[slot]
            slot_of, tables, rids, plens = (
                jnp.asarray(x) for x in (slot_of, tables, rids, plens))
            nchunk = max(1, -(-max(len(seq) - start
                                   for (_, _, start, seq) in group) // Tc))
            for c in range(nchunk):
                tok = np.zeros((R, Tc), np.int32)
                st = np.zeros((R,), np.int32)
                vd = np.zeros((R,), np.int32)
                commit = np.zeros((R,), bool)
                for row, (_, _, start, seq) in enumerate(group):
                    s0 = start + c * Tc
                    v = max(0, min(Tc, len(seq) - s0))
                    st[row] = min(s0, len(seq))
                    vd[row] = v
                    if v:
                        tok[row, :v] = seq[s0:s0 + v]
                        commit[row] = s0 + v == len(seq)
                self._c_prefill.inc(Tc * R)
                with self.obs.span("serve.admit.prefill",
                                   tokens=int(vd.sum()), width=R * Tc,
                                   rows=R):
                    nxt, self.cache, self.seq_lens, self.last_tokens, \
                        self.sample_salt = self._admit_fn(
                            self.params, self.cache, jnp.asarray(tok),
                            jnp.asarray(st), jnp.asarray(vd), tables,
                            slot_of, rids, plens, jnp.asarray(commit),
                            self.seq_lens, self.last_tokens,
                            self.sample_salt)
                if commit.any():
                    firsts.append((nxt, g, commit))
        with self.obs.span("serve.admit.sync"):
            got = jax.device_get([nxt for nxt, _, _ in firsts])
        first = np.zeros((len(rows),), np.int32)
        for toks, (_, g, commit) in zip(got, firsts):
            first[g + np.flatnonzero(commit)] = toks[commit]
        now = time.perf_counter()
        for (slot, r, _, _), tok in zip(rows, first):
            r.out_tokens.append(int(tok))
            r.t_first = now
            if len(r.out_tokens) >= r.max_new_tokens:
                r.done = True
                r.t_done = now
            if self.spec.kv_share:
                self.kvpool.publish(slot)

    def _budgets(self) -> np.ndarray:
        """Decode tokens still owed per slot.  Requests longer than the
        ``max_len`` cache envelope degrade exactly like the legacy engine:
        the KV write clamps to the last row while tokens keep flowing."""
        b = np.zeros((self.slots,), np.int32)
        for i, r in enumerate(self.active):
            if r is None or r.done:
                continue
            b[i] = max(0, r.max_new_tokens - len(r.out_tokens))
        return b

    def _decode_chunk(self, num_steps: int) -> None:
        """One device dispatch advancing every live slot up to ``num_steps``
        tokens; host-side bookkeeping runs once on the returned chunk."""
        budgets = self._budgets()
        live = int(np.count_nonzero(budgets))
        # slots still holding a finished request's rows, which attention
        # skips (`transformer.attended_lens`)
        skipped = sum(1 for r in self.active if r is not None and r.done)
        self._c_skipped.inc(skipped * num_steps)
        with self.obs.span("serve.decode", live=live, steps=num_steps,
                           skipped=skipped):
            t0 = time.perf_counter()
            with self.obs.span("serve.decode.dispatch"):
                extra = (self.tables,) if self._pooled else ()
                toks, self.cache, self.seq_lens, self.last_tokens, load = \
                    self._decode_fn(
                        self.params, self.cache, self.last_tokens,
                        self.seq_lens, jnp.asarray(budgets),
                        self._sample_key, self.sample_salt, *extra,
                        num_steps)
            with self.obs.span("serve.decode.sync"):
                # (num_steps, B) tokens, and the held-expert load beside
                toks, load = jax.device_get((toks, load))
            self._record_latency(time.perf_counter() - t0)
            if load is not None:
                pairs, touched = (int(n) for n in load.sum(axis=(0, 1)))
                with self.obs.span("serve.decode.moe", pairs=pairs,
                                   touched=touched, steps=num_steps):
                    self._c_moe_pairs.inc(pairs)
                    self._c_moe_touched.inc(touched)
            self._steps += num_steps
            with self.obs.span("serve.decode.bookkeeping"):
                now = time.perf_counter()
                for i, r in enumerate(self.active):
                    got = int(min(budgets[i], num_steps))
                    if r is None or r.done or got == 0:
                        continue
                    r.out_tokens.extend(int(t) for t in toks[:got, i])
                    if budgets[i] <= got:            # budget met this chunk
                        r.done = True
                        r.t_done = now

    def _n_active(self) -> int:
        return sum(1 for r in self.active
                   if r is not None and not r.done)

    # -- fleet introspection / migration --------------------------------------
    # The queue-depth/ETA surface the fleet router reads every scheduling
    # decision, and the in-flight export the fleet uses to move requests off
    # a dying replica.  All host-side: no device sync.

    @property
    def n_active(self) -> int:
        """Requests currently occupying decode slots (not yet done)."""
        return self._n_active()

    @property
    def n_pending(self) -> int:
        """Requests submitted but not yet admitted to a slot."""
        return len(self.pending)

    @property
    def free_slots(self) -> int:
        """Slots currently available for admission."""
        return sum(1 for r in self.active if r is None or r.done)

    @property
    def depth(self) -> int:
        """Total requests this engine still owes work to."""
        return self.n_active + self.n_pending

    def tokens_owed(self) -> int:
        """Decode tokens still owed across active + pending requests."""
        owed = int(self._budgets().sum())
        owed += sum(r.max_new_tokens for r in self.pending)
        return owed

    def chunk_time_ema(self, default: float = 0.05) -> float:
        """Smoothed per-dispatch latency (seconds), maintained O(1) per
        chunk — the router reads this per routing decision."""
        return default if self._chunk_ema is None else self._chunk_ema

    # -- telemetry views -------------------------------------------------------
    # The pre-registry counter attributes, now thin read-only views over the
    # registry instruments (same names, same values — existing readers and
    # benchmark arms compare unchanged).

    @property
    def prefill_flops_proxy(self) -> int:
        return self._c_prefill.value

    @property
    def kv_prompt_tokens(self) -> int:
        return self._c_kv_prompt.value

    @property
    def kv_shared_tokens(self) -> int:
        return self._c_kv_shared.value

    @property
    def kv_migrated_shared_blocks(self) -> int:
        return self._c_mig_shared.value

    @property
    def kv_migrated_suffix_blocks(self) -> int:
        return self._c_mig_suffix.value

    def _record_latency(self, lat: float) -> None:
        self.chunk_lat_s.append(lat)
        # `run` resets the list per batch, but a fleet replica steps chunk
        # by chunk for the service's lifetime — bound the history so a
        # long-lived engine doesn't leak (EMA carries the tail)
        if len(self.chunk_lat_s) > 4096:
            del self.chunk_lat_s[:2048]
        self._chunk_ema = (lat if self._chunk_ema is None
                           else 0.7 * self._chunk_ema + 0.3 * lat)

    def expected_ttft_s(self, default_chunk_s: float = 0.05, *,
                        chunk_time_s: Optional[float] = None) -> float:
        """Heuristic TTFT estimate for the NEXT request submitted here: one
        admission dispatch once a slot frees, queued behind the decode work
        already owed (measured in chunk dispatches at the engine's smoothed
        chunk latency — or at ``chunk_time_s`` when the caller accounts time
        itself, e.g. the fleet's deterministic virtual clock).  The router's
        shortest-expected-TTFT policy ranks replicas by this number."""
        per_chunk = (chunk_time_s if chunk_time_s is not None
                     else self.chunk_time_ema(default_chunk_s))
        if self.free_slots > 0 and not self.pending:
            return per_chunk                      # admit next dispatch
        ahead = self.tokens_owed()
        width = max(1, self.slots) * max(1, self.spec.chunk)
        waves = 1.0 + ahead / width
        return per_chunk * waves

    def step_chunk(self) -> int:
        """Admit + advance ONE decode chunk (`spec.chunk` steps); returns the
        number of still-active requests.  The single-dispatch quantum fleet
        replicas advance by — same dataflow as `run`, externally paced."""
        if self._fast:
            with self.obs.span("serve.step_chunk", live=self._n_active(),
                               pending=len(self.pending)):
                self._admit()
                if self._n_active() == 0:
                    return 0
                self._decode_chunk(self.spec.chunk)
                return self._n_active()
        self._admit()
        n = 0
        for _ in range(self.spec.chunk):
            n = self.step()
            if n == 0:
                break
        return n

    def export_inflight(self) -> List[Request]:
        """Remove and return every request still owed tokens (admitted and
        pending), clearing their slots.  Used when a slice dies under the
        engine: the survivors re-prefill ``prompt + out_tokens`` and generate
        the remainder, so no request is lost with its replica.  Exported
        requests leave `queue` too — this engine's stats no longer own them.

        Pooled engines also release every slot's block table and account
        the migration split: only each in-flight request's PRIVATE suffix
        blocks would ship with it (``kv_migrated_suffix_blocks``) — its
        shared-prefix blocks stay behind in this pool's trie (or are
        re-matched from the destination's trie), so a migration moves
        ``suffix/(shared+suffix)`` of the naive KV payload."""
        moved: List[Request] = []
        for i, r in enumerate(self.active):
            if self._pooled and self.kvpool.table(i) is not None:
                if r is not None and not r.done:
                    shared = self.kvpool.shared_blocks(i)
                    self._c_mig_shared.inc(shared)
                    self._c_mig_suffix.inc(self._nb - shared)
                self.kvpool.release(i)
                self._tables_np[i] = self.kvpool.num_blocks
            if r is not None and not r.done:
                moved.append(r)
            self.active[i] = None
        if self._pooled:
            self.tables = jnp.asarray(self._tables_np)
        moved.extend(self.pending)
        self.pending = []
        for r in moved:
            if r in self.queue:
                self.queue.remove(r)
        return moved

    # -- pooled-KV introspection ----------------------------------------------

    def prefix_lookup(self, prompt: np.ndarray) -> int:
        """Shareable prefix TOKENS this engine's trie holds for ``prompt``
        right now (0 when not pooled).  Peek only — no references taken, no
        LRU touch — so the fleet router can score every replica per
        routing decision (the prefix-affinity policy)."""
        if not self._pooled:
            return 0
        seq = np.asarray(prompt, np.int32)[-self.prompt_len:]
        return self.kvpool.match_len(seq) * self.spec.kv_block

    def weight_stream_bytes(self) -> int:
        """HBM weight bytes streamed per decode *step* (every weight is read
        once per step regardless of batch width).  Divide by active slots
        for bytes/token — the meter the quantization benchmark gates on."""
        return QUANT.storage_bytes(self.params)

    def kv_stats(self) -> Dict[str, int]:
        """Sharing/migration counters, plus pool accounting when pooled.
        ``prefill_flops_proxy`` (dispatch width x slots, summed over
        prefill dispatches) is counted on the legacy fast path too, so an
        unshared baseline arm and a pooled arm compare on the same
        meter."""
        s = self.kvpool.stats() if self._pooled else {}
        s.update(
            prefill_flops_proxy=self.prefill_flops_proxy,
            kv_prompt_tokens=self.kv_prompt_tokens,
            kv_shared_tokens=self.kv_shared_tokens,
            kv_migrated_shared_blocks=self.kv_migrated_shared_blocks,
            kv_migrated_suffix_blocks=self.kv_migrated_suffix_blocks,
        )
        return s

    def kv_close(self) -> None:
        """Release every slot table and the prefix trie, then audit the
        pool: asserts every block returned to the free list (the zero-leak
        gate the kv-prefix benchmark enforces)."""
        if not self._pooled:
            return
        self.kvpool.close()
        self._tables_np[:] = self.kvpool.num_blocks
        self.tables = jnp.asarray(self._tables_np)

    def step(self) -> int:
        """One decode step over all slots; returns #active requests.

        Per-token compatibility surface: a chunk of exactly one step, so the
        numerics match ``run`` at any chunk size.  Like ``run``, the fast
        path admits before every step so free slots never starve while
        others are mid-request.
        """
        if self._fast:
            self._admit()
            if self._n_active() == 0:
                return 0
            self._decode_chunk(1)
            return self._n_active()
        if self._n_active() == 0 and not self._admit():
            return 0
        return self._step_legacy()

    def run(self, max_steps: int = 1000) -> Dict[str, float]:
        """Serve until the queue drains; returns latency/throughput stats."""
        self.chunk_lat_s = []
        self._steps = 0
        t0 = time.perf_counter()
        if self._fast:
            while self._steps < max_steps:
                self._admit()
                if self._n_active() == 0:
                    # an admission whose requests all finished at once
                    # leaves slots free for the ones still pending
                    if not self.pending:
                        break
                    continue
                # always dispatch the full chunk: num_steps is static, so a
                # data-dependent remainder would recompile the decode
                # program mid-serve (budgets absorb any overshoot)
                self._decode_chunk(self.spec.chunk)
        else:
            while self._steps < max_steps:
                if self.step() == 0:
                    if not any(not r.done for r in self.queue):
                        break
                    if not self._admit():
                        break
        wall = time.perf_counter() - t0
        done = [r for r in self.queue if r.done]
        produced = sum(len(r.out_tokens) for r in done)
        # latency stats cover only THIS run's completions — a prior warmup
        # run's compile-tainted TTFT must not pollute the percentiles
        # (requests_done/tokens stay cumulative over the queue, as pinned)
        ttfts = [r.t_first - r.t_submit for r in done
                 if r.t_first and r.t_done and r.t_done >= t0]
        return {
            "requests_done": len(done),
            "tokens": produced,
            "wall_s": wall,
            "tokens_per_s": produced / max(wall, 1e-9),
            "mean_ttft_s": float(np.mean(ttfts)) if ttfts else 0.0,
            "p50_ttft_s": _pct(ttfts, 50),
            "p95_ttft_s": _pct(ttfts, 95),
            "decode_steps": self._steps,
            "chunk": self.spec.chunk if self._fast else 1,
            "p50_chunk_s": _pct(self.chunk_lat_s, 50),
            "p95_chunk_s": _pct(self.chunk_lat_s, 95),
        }

    # -- legacy full-batch path (whisper enc-dec cache) -----------------------

    def _admit_full(self) -> bool:
        """Legacy admission: (re)prefill the whole slot batch."""
        free = [i for i, a in enumerate(self.active) if a is None
                or a.done]
        if not self.pending or not free:
            return False
        for i in free:
            if not self.pending:
                break
            self.active[i] = self.pending.pop(0)
        prompts = np.zeros((self.slots, self.prompt_len), np.int32)
        for i, r in enumerate(self.active):
            if r is None:
                continue
            seq = np.concatenate([r.prompt, np.asarray(r.out_tokens,
                                                       np.int32)])
            seq = seq[-self.prompt_len:]
            prompts[i, -len(seq):] = seq
        batch = {"tokens": jnp.asarray(prompts)}
        if self.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (self.slots, self.prompt_len, self.cfg.d_model), jnp.float32)
        logits, self.cache = self._prefill(self.params, batch)
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        now = time.perf_counter()
        for i, r in enumerate(self.active):
            if r is not None and not r.done:
                r.out_tokens.append(int(nxt[i]))
                if r.t_first is None:
                    r.t_first = now
                if len(r.out_tokens) >= r.max_new_tokens:
                    r.done = True
                    r.t_done = now
        self.last_tokens = jnp.asarray(nxt)
        return True

    def _step_legacy(self) -> int:
        t0 = time.perf_counter()
        logits, self.cache = self._decode(
            self.params, self.cache, self.last_tokens)
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self._record_latency(time.perf_counter() - t0)
        self._steps += 1
        n_active = 0
        now = time.perf_counter()
        for i, r in enumerate(self.active):
            if r is None or r.done:
                continue
            r.out_tokens.append(int(nxt[i]))
            if len(r.out_tokens) >= r.max_new_tokens:
                r.done = True
                r.t_done = now
            else:
                n_active += 1
        self.last_tokens = jnp.asarray(nxt)
        return n_active
