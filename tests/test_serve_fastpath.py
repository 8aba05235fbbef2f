"""Serve fast path: incremental admission, chunked on-device decode, and
the continuous-batching invariants.

Covers the PR-3 contract:
  * ``Request`` has identity equality (``eq=False``) — value-equal numpy
    prompts must never crash membership tests during admission;
  * chunked decode is numerics-neutral: greedy outputs are bitwise identical
    for every ``chunk``, including the per-token path (chunk=1) and the
    ``step()`` compatibility surface;
  * admission/retirement invariants under randomized schedules (property
    test): no token loss, no decode of retired slots, FIFO admission.
"""
import dataclasses

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import registry
from repro.models import api
from repro.parallel.context import LOCAL as LOCAL_CTX
from repro.serve.engine import Request, ServeEngine, SliceSpec


_MODEL = {}


def _model():
    """Module-memoized reduced model (plain function, not a fixture, so the
    hypothesis-shim property tests can use it too)."""
    if not _MODEL:
        cfg = registry.get_reduced("olmo-1b")
        _MODEL["m"] = (cfg, api.init_params(cfg, jax.random.PRNGKey(0)))
    return _MODEL["m"]


@pytest.fixture(scope="module")
def small_model():
    return _model()


class TestRequestIdentity:
    def test_eq_is_identity_not_value(self):
        """dataclass(eq=False): value-equal requests stay distinct and
        membership tests never hit ambiguous ndarray comparison."""
        a = Request(rid=0, prompt=np.arange(4), max_new_tokens=4)
        b = Request(rid=1, prompt=np.arange(4), max_new_tokens=4)
        assert a != b and a == a
        assert a in [a, b] and b in [a, b]
        assert Request(rid=2, prompt=np.arange(4),
                       max_new_tokens=4) not in [a, b]

    def test_no_generated_eq(self):
        """Pin eq=False: the dataclass must not synthesize an elementwise
        ``__eq__`` (it would raise on value-equal ndarray prompts)."""
        assert Request.__eq__ is object.__eq__
        assert Request.__hash__ is object.__hash__

    def test_duplicate_prompts_serve_cleanly(self, small_model):
        """The admission scan (`r not in self.active`) used to be able to
        raise on value-equal prompts; serving two identical prompts must
        work and both must finish."""
        cfg, params = small_model
        eng = ServeEngine(cfg, params, SliceSpec(slots=1, max_len=32,
                                                 prompt_len=8))
        r1 = eng.submit(np.arange(6), max_new_tokens=4)
        r2 = eng.submit(np.arange(6), max_new_tokens=4)
        stats = eng.run()
        assert stats["requests_done"] == 2
        assert r1.done and r2.done
        assert r1.out_tokens == r2.out_tokens   # same prompt, greedy


def _serve_outputs(small_model, chunk):
    cfg, params = small_model
    eng = ServeEngine(cfg, params, SliceSpec(
        slots=2, max_len=48, prompt_len=8, chunk=chunk))
    reqs = [eng.submit(np.arange(5) + i, max_new_tokens=7)
            for i in range(5)]
    stats = eng.run()
    assert stats["requests_done"] == 5 and stats["tokens"] == 35
    return [tuple(r.out_tokens) for r in reqs]


@pytest.fixture(scope="module")
def per_token_outputs(small_model):
    return _serve_outputs(small_model, chunk=1)


class TestChunkEquivalence:
    @pytest.mark.parametrize("chunk", [2, 3, 8, 32])
    def test_greedy_outputs_bitwise_identical(self, small_model,
                                              per_token_outputs, chunk):
        assert _serve_outputs(small_model, chunk) == per_token_outputs

    def test_step_matches_run(self, small_model):
        """The per-token step() surface is the chunk=1 program."""
        cfg, params = small_model
        outs = []
        for use_step in (False, True):
            eng = ServeEngine(cfg, params, SliceSpec(
                slots=2, max_len=32, prompt_len=8, chunk=4))
            reqs = [eng.submit(np.arange(4) + i, max_new_tokens=5)
                    for i in range(3)]
            if use_step:
                while any(not r.done for r in reqs):
                    eng.step()
            else:
                eng.run()
            outs.append([tuple(r.out_tokens) for r in reqs])
        assert outs[0] == outs[1]

    def test_sampling_chunk_invariant(self, small_model):
        """Sampled decode folds the key per (request, position), so outputs
        are chunk-invariant too (same engine seed)."""
        cfg, params = small_model
        outs = []
        for chunk in (1, 4):
            eng = ServeEngine(cfg, params, SliceSpec(
                slots=2, max_len=32, prompt_len=8, greedy=False,
                chunk=chunk))
            reqs = [eng.submit(np.arange(4) + i, max_new_tokens=6)
                    for i in range(2)]
            eng.run()
            outs.append([tuple(r.out_tokens) for r in reqs])
        assert outs[0] == outs[1]

    def test_sampling_applies_to_first_token(self, small_model):
        """greedy=False must sample the admission-produced first token too
        (not silently argmax it), drawing with the documented
        fold_in(fold_in(key, rid), position) scheme so it composes with
        decode_n's (salt, position) stream without collisions."""
        import jax.numpy as jnp

        cfg, params = small_model
        eng = ServeEngine(cfg, params, SliceSpec(
            slots=1, max_len=32, prompt_len=8, greedy=False, chunk=2))
        r = eng.submit(np.arange(6), max_new_tokens=1)
        eng.run()
        prompt = np.zeros((1, 8), np.int32)
        prompt[0, -6:] = np.arange(6)
        logits, _ = api.prefill(cfg, params,
                                {"tokens": jnp.asarray(prompt)}, max_len=32)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(1), r.rid), 8)
        want = int(jax.random.categorical(key, logits[0]))
        assert r.out_tokens[0] == want


class TestContinuousBatchingInvariants:
    """Property tests over randomized request schedules."""

    @settings(max_examples=6, deadline=None)
    @given(st.integers(1, 3),                       # slots
           st.lists(st.tuples(st.integers(1, 9),    # prompt len
                              st.integers(1, 7)),   # max_new_tokens
                    min_size=1, max_size=7))
    def test_no_token_loss_and_fifo(self, slots, reqspecs):
        cfg, params = _model()
        eng = ServeEngine(cfg, params, SliceSpec(
            slots=slots, max_len=32, prompt_len=8, chunk=4))
        reqs = [eng.submit(np.arange(plen, dtype=np.int32) % cfg.vocab_size,
                           max_new_tokens=mnt)
                for plen, mnt in reqspecs]
        stats = eng.run()
        # no token loss: every request completed with exactly its budget
        assert stats["requests_done"] == len(reqs)
        for r in reqs:
            assert r.done and len(r.out_tokens) == r.max_new_tokens
            assert r.t_first is not None and r.t_done is not None
            assert r.t_done >= r.t_first >= r.t_submit
        # FIFO admission: first-token times are non-decreasing in
        # submission order
        firsts = [r.t_first for r in reqs]
        assert firsts == sorted(firsts)
        # retired slots stay retired: every active slot entry is done
        assert all(r is None or r.done for r in eng.active)

    @settings(max_examples=3, deadline=None)
    @given(st.integers(2, 4))
    def test_no_decode_of_retired_slots(self, chunk):
        """A retired request's token list must never grow after t_done —
        the done-mask freezes its slot while others continue."""
        cfg, params = _model()
        eng = ServeEngine(cfg, params, SliceSpec(
            slots=2, max_len=32, prompt_len=8, chunk=chunk))
        short = eng.submit(np.arange(4), max_new_tokens=2)
        long = eng.submit(np.arange(4) + 1, max_new_tokens=11)
        snapshot = None
        while not (short.done and long.done):
            eng.step()
            if short.done and snapshot is None:
                snapshot = list(short.out_tokens)
        assert short.out_tokens == snapshot
        assert len(short.out_tokens) == 2 and len(long.out_tokens) == 11

    def test_late_submission_reuses_retired_slot(self, small_model):
        """Submitting after a drain admits into retired slots without
        touching live state."""
        cfg, params = small_model
        eng = ServeEngine(cfg, params, SliceSpec(slots=1, max_len=32,
                                                 prompt_len=8, chunk=4))
        r1 = eng.submit(np.arange(4), max_new_tokens=3)
        eng.run()
        assert r1.done
        r2 = eng.submit(np.arange(4) + 2, max_new_tokens=5)
        stats = eng.run()
        assert r2.done and len(r2.out_tokens) == 5
        assert stats["requests_done"] == 2     # cumulative over the queue


class TestStatsSurface:
    def test_run_reports_percentiles_and_chunk(self, small_model):
        cfg, params = small_model
        eng = ServeEngine(cfg, params, SliceSpec(slots=2, max_len=32,
                                                 prompt_len=8, chunk=4))
        for i in range(3):
            eng.submit(np.arange(4) + i, max_new_tokens=4)
        stats = eng.run()
        for k in ("p50_ttft_s", "p95_ttft_s", "p50_chunk_s", "p95_chunk_s",
                  "mean_ttft_s", "tokens_per_s", "decode_steps"):
            assert k in stats, k
        assert stats["chunk"] == 4
        assert stats["p95_ttft_s"] >= stats["p50_ttft_s"] >= 0.0
        assert stats["p95_chunk_s"] >= stats["p50_chunk_s"] > 0.0


class TestExportInflightRoundTrip:
    """Pin the migration contract at its sharpest edge: a request exported
    while admitted-but-zero-decoded (its only token came from the admission
    dispatch) must round-trip exactly — the survivor re-prefills
    ``prompt + out_tokens`` and serves precisely the remainder, no token
    lost, none double-served."""

    SPEC = SliceSpec(slots=2, max_len=64, prompt_len=16, chunk=4)

    def _roundtrip(self, cfg, params, spec, prompt, n):
        ref_eng = ServeEngine(cfg, params, spec)
        ref = ref_eng.submit(prompt, max_new_tokens=n)
        ref_eng.run()

        e1 = ServeEngine(cfg, params, spec)
        r = e1.submit(prompt, max_new_tokens=n)
        e1._admit()                       # admission token only, no decode
        assert len(r.out_tokens) == 1 and not r.done
        moved = e1.export_inflight()
        assert moved == [r]

        e2 = ServeEngine(cfg, params, spec)
        cont = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(r.out_tokens, np.int32)])
        r2 = e2.submit(cont, max_new_tokens=n - len(r.out_tokens))
        e2.run()
        return ref, r.out_tokens + r2.out_tokens

    def test_zero_decoded_export_roundtrips_exactly(self, small_model):
        cfg, params = small_model
        prompt = np.arange(10, dtype=np.int32) + 3
        ref, total = self._roundtrip(cfg, params, self.SPEC, prompt, 6)
        assert len(total) == 6                       # count-exact: no
        assert len(ref.out_tokens) == 6              # off-by-one either way
        # prompt (10) + admission token fits the 16-token window, so the
        # re-prefilled continuation is conditioned on the same context and
        # greedy decode reproduces the uninterrupted stream
        assert total == ref.out_tokens

    def test_pending_export_keeps_full_budget(self, small_model):
        """A request exported before ANY dispatch re-prefills the bare
        prompt and owes its full budget."""
        cfg, params = small_model
        eng = ServeEngine(cfg, params, self.SPEC)
        r = eng.submit(np.arange(6), max_new_tokens=5)
        moved = eng.export_inflight()
        assert moved == [r] and r.out_tokens == []
        e2 = ServeEngine(cfg, params, self.SPEC)
        r2 = e2.submit(r.prompt, max_new_tokens=5)
        e2.run()
        assert len(r2.out_tokens) == 5

    def test_zero_decoded_export_roundtrips_pooled(self, small_model):
        """Same edge over the pooled prefix-shared KV engine; the export
        must also release every block table (audited by kv_close)."""
        cfg, params = small_model
        spec = SliceSpec(slots=2, max_len=64, prompt_len=16, chunk=4,
                         kv_block=8, suffix_len=8)
        prompt = np.arange(10, dtype=np.int32) + 3
        ref, total = self._roundtrip(cfg, params, spec, prompt, 6)
        assert len(total) == 6 and len(ref.out_tokens) == 6
        assert total == ref.out_tokens
        # the exporting engine in _roundtrip released its tables on export;
        # a fresh engine repeating the admit+export must audit clean
        e = ServeEngine(cfg, params, spec)
        e.submit(prompt, max_new_tokens=6)
        e._admit()
        e.export_inflight()
        e.kv_close()                       # asserts zero blocks leaked


class TestPooledPrefixKV:
    """Pooled prefix-shared KV engine (serve/kvpool.py): greedy outputs are
    bitwise-identical to the dense fast path AND between the shared and
    unshared pooled arms, while sharing strictly reduces the prefill-cost
    proxy under a common-header mix."""

    def _prompts(self, cfg, n=6):
        rng = np.random.RandomState(11)
        header = rng.randint(0, cfg.vocab_size, (24,)).astype(np.int32)
        out = []
        for i in range(n):
            tail = rng.randint(0, cfg.vocab_size,
                               (rng.randint(3, 12),)).astype(np.int32)
            out.append(np.concatenate([header, tail]) if i % 3 != 2
                       else rng.randint(0, cfg.vocab_size,
                                        (20,)).astype(np.int32))
        return header, out

    def _run(self, cfg, params, spec, prompts):
        eng = ServeEngine(cfg, params, spec)
        for p in prompts:
            eng.submit(p, max_new_tokens=5)
        eng.run(max_steps=500)
        return eng, [list(r.out_tokens) for r in eng.queue]

    def test_pooled_matches_dense_and_share_is_bitwise(self, small_model):
        cfg, params = small_model
        _, prompts = self._prompts(cfg)
        base = dict(slots=3, max_len=64, prompt_len=40, chunk=4)
        _, dense = self._run(cfg, params, SliceSpec(**base), prompts)
        share_eng, share = self._run(
            cfg, params, SliceSpec(**base, kv_block=8, suffix_len=8),
            prompts)
        noshare_eng, noshare = self._run(
            cfg, params, SliceSpec(**base, kv_block=8, suffix_len=8,
                                   kv_share=False), prompts)
        assert share == noshare          # sharing is bitwise-invisible
        assert share == dense            # pooled == dense fast path
        assert (share_eng.prefill_flops_proxy
                < noshare_eng.prefill_flops_proxy)
        assert share_eng.kv_shared_tokens > 0
        share_eng.kv_close()             # zero blocks leaked
        noshare_eng.kv_close()

    # gemma2-9b reduced (sliding window, global_every, softcaps, post_norm)
    # with post-norm gains of 8, so that the layers, not the scaled tied
    # embedding, choose the tokens.  Its pooled and per-slot prefills sum
    # attention in different orders, and the tokens then part; the pooled
    # tokens are pinned instead
    GAIN8_POOLED = [[145, 145, 145, 194, 440], [440, 440, 440, 440, 440],
                    [212, 58, 58, 58, 58], [145, 145, 145, 145, 145],
                    [393, 393, 393, 393, 393], [359, 359, 359, 359, 359]]

    @pytest.mark.parametrize("gain", [1.0, 8.0], ids=["drawn", "gain8"])
    def test_pooled_tokens_on_a_local_global_stack(self, gain):
        """The decode loop scans gemma2's local/global layers in pairs, a
        static window each; sharing stays bitwise-invisible, and as drawn
        the pooled tokens are the per-slot path's."""
        cfg = registry.get_reduced("gemma2-9b")
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        for k in ("post_ln1", "post_ln2"):
            params["layers"][k]["w"] = params["layers"][k]["w"] + gain - 1
        _, prompts = self._prompts(cfg)
        base = dict(slots=3, max_len=64, prompt_len=40, chunk=4)
        share_eng, share = self._run(
            cfg, params, SliceSpec(**base, kv_block=8, suffix_len=8),
            prompts)
        noshare_eng, noshare = self._run(
            cfg, params, SliceSpec(**base, kv_block=8, suffix_len=8,
                                   kv_share=False), prompts)
        want = (self._run(cfg, params, SliceSpec(**base), prompts)[1]
                if gain == 1 else self.GAIN8_POOLED)
        assert share == noshare == want
        share_eng.kv_close()
        noshare_eng.kv_close()

    def test_prefix_lookup_scores_published_header(self, small_model):
        cfg, params = small_model
        header, prompts = self._prompts(cfg)
        spec = SliceSpec(slots=3, max_len=64, prompt_len=40, chunk=4,
                         kv_block=8, suffix_len=8)
        eng, _ = self._run(cfg, params, spec, prompts)
        probe = np.concatenate([header, header[:5]])
        assert eng.prefix_lookup(probe) >= 16    # header blocks resident
        assert eng.prefix_lookup(header[::-1].copy()) == 0
        eng.kv_close()


def _gather_once_decode(cfg, params, pool, tokens, lens, budget, tables,
                        num_steps, ctx=None):
    """The pooled decode as it ran before the in-place form, kept as the
    oracle: gather each slot's logical view out of the pool, decode on it
    with the per-slot dense path, then scatter the chunk's rows back —
    slot b wrote row lens+i at step i < min(budget, num_steps), clamped to
    the last lane on overflow with the last write winning."""
    import jax.numpy as jnp
    from repro.models import transformer as TF
    from repro.parallel.context import LOCAL

    Ls, NB, bs, KH, hd = pool.k.shape
    B, nb = tables.shape
    W = nb * bs
    gidx = ((jnp.clip(tables, 0, NB - 1) * bs)[:, :, None]
            + jnp.arange(bs)).reshape(-1)
    kf = pool.k.reshape(Ls, NB * bs, KH, hd)
    vf = pool.v.reshape(Ls, NB * bs, KH, hd)
    view = TF.Cache(k=kf[:, gidx].reshape(Ls, B, W, KH, hd),
                    v=vf[:, gidx].reshape(Ls, B, W, KH, hd), pos=pool.pos)
    toks, view, seq_lens, last = TF.decode_n(
        cfg, params, view, tokens, lens, budget, ctx or LOCAL,
        num_steps=num_steps)
    nsteps = jnp.minimum(budget, num_steps)
    i = jnp.arange(num_steps)
    rows = lens[:, None] + i[None, :]
    rowc = jnp.minimum(rows, W - 1)
    keep = ((i[None, :] < nsteps[:, None])
            & ((rows < W - 1) | (i[None, :] == nsteps[:, None] - 1)))
    phys = jnp.take_along_axis(tables, rowc // bs, axis=1)
    dest = jnp.where(keep, phys * bs + rowc % bs, NB * bs).reshape(-1)
    ridx = rowc[None, :, :, None, None]
    newk = jnp.take_along_axis(view.k, ridx, axis=2).reshape(Ls, -1, KH, hd)
    newv = jnp.take_along_axis(view.v, ridx, axis=2).reshape(Ls, -1, KH, hd)
    pool = TF.Cache(k=kf.at[:, dest].set(newk).reshape(pool.k.shape),
                    v=vf.at[:, dest].set(newv).reshape(pool.v.shape),
                    pos=view.pos)
    return toks, pool, seq_lens, last


class TestPooledDecodeInPlace:
    """`decode_n` with block tables decodes on the KV pool in place: the
    same tokens, lengths and pool contents as the gather-once algorithm,
    and nothing but each live slot's fresh rows written."""

    NUM_STEPS, BS, NB = 6, 4, 24
    # slot: (table, seq_len, budget) — 0 unadmitted (its budget is the
    # test's parameter), 1 done mid-chunk, 2 overflows into the last lane,
    # 3 and 4 share prefix block 8; blocks 15-23 belong to nobody
    SLOTS = [([NB] * 4, 0, None), ([0, 1, 2, 3], 5, 3),
             ([4, 5, 6, 7], 13, 8), ([8, 9, 10, 11], 5, 6),
             ([8, 12, 13, 14], 7, 6)]

    def _inputs(self, cfg, unadmitted_budget):
        import jax.numpy as jnp
        from repro.models import transformer as TF

        a = cfg.attention
        shape = (cfg.num_layers, self.NB, self.BS, a.num_kv_heads,
                 a.head_dim)
        kk, kv, kt = jax.random.split(jax.random.PRNGKey(5), 3)
        pool = TF.Cache(k=jax.random.normal(kk, shape, jnp.bfloat16),
                        v=jax.random.normal(kv, shape, jnp.bfloat16),
                        pos=jnp.asarray(3, jnp.int32))
        tables = jnp.asarray([s[0] for s in self.SLOTS], jnp.int32)
        lens = jnp.asarray([s[1] for s in self.SLOTS], jnp.int32)
        budget = jnp.asarray([unadmitted_budget]
                             + [s[2] for s in self.SLOTS[1:]], jnp.int32)
        tokens = jax.random.randint(kt, (len(self.SLOTS),), 0,
                                    cfg.vocab_size, jnp.int32)
        return pool, tokens, lens, budget, tables

    def _written(self, cfg, lens, budget):
        """(layer, block, row) of every row the chunk may write."""
        W = 4 * self.BS
        out = set()
        for b, (table, _, _) in enumerate(self.SLOTS):
            if table[0] >= self.NB:
                continue
            for i in range(min(int(budget[b]), self.NUM_STEPS)):
                r = min(int(lens[b]) + i, W - 1)
                out |= {(l, table[r // self.BS], r % self.BS)
                        for l in range(cfg.num_layers)}
        return out

    @pytest.mark.parametrize("unadmitted_budget", [0, 3],
                             ids=["idle", "live_unadmitted"])
    def test_matches_gather_once_bitwise(self, small_model,
                                         unadmitted_budget):
        cfg, params = small_model
        pool, tokens, lens, budget, tables = self._inputs(
            cfg, unadmitted_budget)
        want = _gather_once_decode(cfg, params, pool, tokens, lens, budget,
                                   tables, self.NUM_STEPS)
        got = api.decode_n(cfg, params, pool, tokens, lens, budget,
                           num_steps=self.NUM_STEPS, tables=tables)
        # an unadmitted slot given a budget decodes on whatever its clamped
        # table names; the gather-once view kept its writes, the pool drops
        # them, so only the admitted slots' tokens must agree then
        first = 1 if unadmitted_budget else 0
        np.testing.assert_array_equal(np.asarray(got[0])[:, first:],
                                      np.asarray(want[0])[:, first:])
        np.testing.assert_array_equal(np.asarray(got[3])[first:],
                                      np.asarray(want[3])[first:])
        np.testing.assert_array_equal(np.asarray(got[2]),
                                      np.asarray(want[2]))
        for x, y in ((got[1].k, want[1].k), (got[1].v, want[1].v),
                     (got[1].pos, want[1].pos)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

        # nothing outside the live slots' fresh rows moved: no block of
        # another layer, of the unadmitted slot, of the shared prefix (8)
        # or of the unowned tail (15-23)
        written = self._written(cfg, lens, budget)
        for new, old in ((got[1].k, pool.k), (got[1].v, pool.v)):
            moved = np.any(np.asarray(new) != np.asarray(old), axis=(3, 4))
            assert set(zip(*np.nonzero(moved))) == written

    @pytest.mark.parametrize("decode_attn", ["dense", "paged"])
    def test_no_logical_view_materialised(self, small_model, decode_attn):
        """Neither the XLA reference nor the kernel path of the pooled
        chunk holds a value the size of every slot's logical view over
        every layer; the gather-once oracle does."""
        cfg, params = small_model
        ctx = dataclasses.replace(LOCAL_CTX, decode_attn=decode_attn)
        pool, tokens, lens, budget, tables = self._inputs(cfg, 0)
        a = cfg.attention
        view = (cfg.num_layers * len(self.SLOTS) * 4 * self.BS
                * a.num_kv_heads * a.head_dim)

        def sizes(fn):
            jaxpr = jax.make_jaxpr(fn)(pool, tokens, lens, budget, tables)
            return set(_out_sizes(jaxpr.jaxpr))

        assert view in sizes(lambda *xs: _gather_once_decode(
            cfg, params, *xs, num_steps=self.NUM_STEPS, ctx=ctx))
        assert view not in sizes(
            lambda pool, t, n, b, tb: api.decode_n(
                cfg, params, pool, t, n, b, ctx, num_steps=self.NUM_STEPS,
                tables=tb))


def _full_lens(seq_lens, active, width):
    """The rule before `transformer.attended_lens`, kept as the oracle:
    attention reads every slot's rows, decoding or not."""
    import jax.numpy as jnp

    return jnp.minimum(seq_lens + 1, width)


class TestDoneSlotsSkipAttention:
    """Attention reads no rows of a slot that is not decoding
    (`transformer.attended_lens`): the chunk's tokens, lengths, pool and
    conv state are bitwise those of the rule that read every slot's rows,
    on a dense stack, a conv/GQA stack with held experts and a windowed
    local/global stack, through the XLA reference and the kernel."""

    NUM_STEPS, BS, NB, NBT = 5, 4, 40, 8
    # slot: (table, seq_len, budget) — 0 live, 1 finished holding a long
    # cache, 2 unadmitted, 3 finishes mid-chunk, 4 live with a long cache
    # (past gemma2's window of 16); blocks 32-39 belong to nobody
    SLOTS = [(list(range(0, 8)), 6, 5), (list(range(8, 16)), 29, 0),
             ([NB] * NBT, 0, 0), (list(range(16, 24)), 11, 2),
             (list(range(24, 32)), 20, 5)]

    def _model(self, name):
        cfg = registry.get_reduced(name)
        if cfg.moe is not None:
            cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                      held_experts=4))
        return cfg, api.init_params(cfg, jax.random.PRNGKey(0))

    def _inputs(self, cfg):
        import jax.numpy as jnp

        B = len(self.SLOTS)
        pool = api.init_kv_pool(cfg, self.NB, self.BS, slots=B)
        kk, kv, kc, kt = jax.random.split(jax.random.PRNGKey(6), 4)
        pool = dataclasses.replace(
            pool, k=jax.random.normal(kk, pool.k.shape, pool.k.dtype),
            v=jax.random.normal(kv, pool.v.shape, pool.v.dtype),
            conv=(None if pool.conv is None else jax.random.normal(
                kc, pool.conv.shape, pool.conv.dtype)))
        tables = jnp.asarray([s[0] for s in self.SLOTS], jnp.int32)
        lens = jnp.asarray([s[1] for s in self.SLOTS], jnp.int32)
        budget = jnp.asarray([s[2] for s in self.SLOTS], jnp.int32)
        tokens = jax.random.randint(kt, (B,), 0, cfg.vocab_size, jnp.int32)
        return pool, tokens, lens, budget, tables

    def test_rule_reads_no_rows_of_idle_slots(self):
        import jax.numpy as jnp

        from repro.models import transformer as TF

        lens = TF.attended_lens(jnp.asarray([3, 40, 7, 31]),
                                jnp.asarray([True, False, True, True]), 32)
        np.testing.assert_array_equal(np.asarray(lens), [4, 0, 8, 32])

    @pytest.mark.parametrize("decode_attn", ["dense", "paged"])
    @pytest.mark.parametrize("name", ["olmo-1b", "lfm2-8b-a1b", "gemma2-9b"])
    def test_pooled_matches_full_lengths_bitwise(self, name, decode_attn,
                                                 monkeypatch):
        from repro.models import transformer as TF

        cfg, params = self._model(name)
        ctx = dataclasses.replace(LOCAL_CTX, decode_attn=decode_attn)
        pool, tokens, lens, budget, tables = self._inputs(cfg)

        def run():
            return api.decode_n(cfg, params, pool, tokens, lens, budget, ctx,
                                num_steps=self.NUM_STEPS, tables=tables)

        got = run()
        monkeypatch.setattr(TF, "attended_lens", _full_lens)
        want = run()
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        # the live slots decoded, the finished and unadmitted ones did not
        np.testing.assert_array_equal(
            np.asarray(got[2]), np.asarray(lens) + np.minimum(
                np.asarray(budget), self.NUM_STEPS))

    def test_per_slot_matches_full_lengths(self, small_model, monkeypatch):
        """`decode_step_paged` keeps the same contract: tokens, lengths and
        every slot's valid rows as under full lengths (a frozen slot's
        garbage row past its length is written either way, from its own
        discarded lane)."""
        import jax.numpy as jnp

        from repro.models import transformer as TF

        cfg, params = small_model
        pool, tokens, lens, budget, _ = self._inputs(cfg)
        a, B, W = cfg.attention, len(self.SLOTS), self.NBT * self.BS
        shape = (cfg.num_layers, B, W, a.num_kv_heads, a.head_dim)
        kk, kv = jax.random.split(jax.random.PRNGKey(7))
        cache = TF.Cache(k=jax.random.normal(kk, shape, jnp.bfloat16),
                         v=jax.random.normal(kv, shape, jnp.bfloat16),
                         pos=jnp.asarray(0, jnp.int32))

        def run():
            return api.decode_n(cfg, params, cache, tokens, lens, budget,
                                num_steps=self.NUM_STEPS)

        got = run()
        monkeypatch.setattr(TF, "attended_lens", _full_lens)
        want = run()
        for i in (0, 2, 3):
            np.testing.assert_array_equal(np.asarray(got[i]),
                                          np.asarray(want[i]))
        valid = np.arange(W)[None, :] < np.asarray(got[2])[:, None]
        for x, y in ((got[1].k, want[1].k), (got[1].v, want[1].v)):
            np.testing.assert_array_equal(np.asarray(x)[:, valid],
                                          np.asarray(y)[:, valid])

    @pytest.mark.parametrize("k", [1, 2])
    def test_skipped_slots_counted(self, small_model, k):
        """A chunk that starts with k finished slots records ``skipped=k``
        on its ``serve.decode`` span and adds k x steps to
        ``serve.decode_slots_skipped``."""
        from repro.obs import Telemetry

        cfg, params = small_model
        obs = Telemetry(tracing=True)
        eng = ServeEngine(cfg, params, SliceSpec(
            slots=3, max_len=64, prompt_len=16, chunk=4, kv_block=8,
            kv_share=False, suffix_len=8), obs=obs)
        # k requests end inside the first chunk, the rest outlast two
        for i in range(3):
            eng.submit(np.arange(5) + i, max_new_tokens=3 if i < k else 12)
        eng.step_chunk()
        eng.step_chunk()
        spans = [s for s in obs.tracer.spans if s.name == "serve.decode"]
        assert [s.args["skipped"] for s in spans] == [0, k]
        assert all(s.args["steps"] == 4 for s in spans)
        assert obs.metrics.counter(
            "serve.decode_slots_skipped").value == k * 4


class TestPoolRidesTheCarry:
    """The pooled prefill and decode keep the pool in their layer scans'
    carry: no scan takes the pool, or one layer's share of it, as ``xs`` or
    hands it back as ``ys``, so no scan slices it in or stacks a copy of it
    out.  (A run that leaves the pool alone, such as lfm2's conv layers,
    may hold it as a loop-invariant const, which moves nothing.)"""

    B, T, BS, NB, NBT = 2, 8, 4, 13, 4      # NB = 13: no weight matches

    def _model(self, name):
        cfg = registry.get_reduced(name)
        if cfg.moe is not None:
            cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                      held_experts=4))
        return cfg, api.init_params(cfg, jax.random.PRNGKey(0))

    @pytest.mark.parametrize("name", ["olmo-1b", "lfm2-8b-a1b"])
    @pytest.mark.parametrize("path", ["prefill", "decode"])
    def test_no_scan_moves_the_pool(self, name, path):
        import jax.numpy as jnp

        cfg, params = self._model(name)
        B = self.B
        pool = api.init_kv_pool(cfg, self.NB, self.BS, slots=B)
        tables = jnp.arange(B * self.NBT, dtype=jnp.int32).reshape(B, -1)
        if path == "prefill":
            def fn(c, tb):
                return api.prefill_suffix(
                    cfg, params, c, jnp.ones((B, self.T), jnp.int32),
                    jnp.zeros((B,), jnp.int32), jnp.full((B,), 5), tb)
        else:
            def fn(c, tb):
                return api.decode_n(
                    cfg, params, c, jnp.ones((B,), jnp.int32),
                    jnp.full((B,), 5), jnp.full((B,), 3), num_steps=3,
                    tables=tb)
        layer = int(np.prod(pool.k.shape[1:]))
        sizes = {layer, pool.k.shape[0] * layer}
        jaxpr = jax.make_jaxpr(fn)(pool, tables)
        moved = set(_scan_io_sizes(jaxpr.jaxpr))
        assert moved and not sizes & moved


def _scan_io_sizes(jaxpr):
    """Element counts of every scan's inputs past its consts and carry
    (xs) and of its outputs past the carry (ys), nested scans included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            for v in eqn.invars[nc + nk:] + eqn.outvars[nk:]:
                yield int(np.prod(v.aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scan_io_sizes(inner)


def _out_sizes(jaxpr):
    """Element counts of every equation's outputs, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield int(np.prod(v.aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _out_sizes(inner)


class TestAdmissionRows:
    """A pooled admission dispatch carries one admitted request per row,
    and as many rows as reach the ridge of the weights' bytes
    (`admission_rows`), not one row per slot: a wave of fewer requests
    computes fewer rows, with the same tokens, in one compiled program."""

    SPEC = SliceSpec(slots=3, max_len=64, prompt_len=40, chunk=4,
                     kv_block=8, kv_share=False, suffix_len=8)

    @pytest.mark.parametrize("mix", ["chat", "reason"])
    @pytest.mark.parametrize("weight_bytes", [4, 2, 1],
                             ids=["f32", "bf16", "int8"])
    def test_rule_gives_one_row_to_the_serving_cells(self, mix,
                                                     weight_bytes):
        import json
        import pathlib

        from repro.serve.engine import admission_rows

        path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
                / "traffic" / f"{mix}.json")
        spec = SliceSpec(**json.loads(path.read_text())["engine"])
        assert admission_rows(spec.slots, spec.suffix_len,
                              weight_bytes) == 1

    def test_rule_keeps_every_slot_for_short_rows(self, small_model):
        from repro.serve.engine import admission_rows

        assert admission_rows(2, 8, 4) == 2
        assert admission_rows(2, 8, 2) == 2
        # the engine reads its weights' bytes: float32 here
        cfg, params = small_model
        wide = dataclasses.replace(self.SPEC, suffix_len=512, prompt_len=64)
        assert ServeEngine(cfg, params, wide)._rows == 1
        assert ServeEngine(cfg, params, self.SPEC)._rows == 3

    def _engine(self, cfg, params, spec, rows, monkeypatch, obs=None):
        from repro.serve import engine as ENGINE

        if rows is not None:
            monkeypatch.setattr(ENGINE, "admission_rows", lambda *a: rows)
        eng = ServeEngine(cfg, params, spec, obs=obs)
        if rows is not None:
            assert eng._rows == rows
        return eng

    def _prompts(self, cfg):
        rng = np.random.RandomState(5)
        # 20 tokens span three 8-token chunks
        return [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                for n in (11, 4, 17, 20, 9, 14)]

    def _serve(self, eng, prompts, check_admission=None):
        """Waves of 3, then 1 (a three-chunk prompt), then 2 while that
        one is mid-decode."""
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts[:3]]
        while any(not r.done for r in reqs):
            eng.step_chunk()
        reqs.append(eng.submit(prompts[3], max_new_tokens=12))
        eng.step_chunk()
        eng.step_chunk()
        reqs += [eng.submit(p, max_new_tokens=6) for p in prompts[4:]]
        if check_admission:
            check_admission(eng)
        while any(not r.done for r in reqs):
            eng.step_chunk()
        return [list(r.out_tokens) for r in reqs]

    def test_tokens_match_across_row_counts_and_dense(self, small_model,
                                                      monkeypatch):
        cfg, params = small_model
        prompts = self._prompts(cfg)
        dense = self._serve(ServeEngine(cfg, params, dataclasses.replace(
            self.SPEC, kv_block=0)), prompts)
        every = self._serve(self._engine(cfg, params, self.SPEC, None,
                                         monkeypatch), prompts)

        def untouched(eng):
            """Admitting two requests leaves the decoding slot's pool
            rows, length and last token, and every block the admitted
            slots do not own, as they were."""
            live = [i for i, r in enumerate(eng.active)
                    if r is not None and not r.done]
            assert len(live) == 1
            before = [np.array(x) for x in (eng.cache.k, eng.cache.v,
                                            eng.seq_lens, eng.last_tokens)]
            eng._admit()
            admitted = [i for i in range(eng.slots) if i not in live]
            owned = {int(b) for i in admitted for b in eng._tables_np[i]}
            keep = [b for b in range(eng.kvpool.num_blocks)
                    if b not in owned]
            assert set(eng._tables_np[live[0]]) <= set(keep)
            after = [np.array(x) for x in (eng.cache.k, eng.cache.v,
                                           eng.seq_lens, eng.last_tokens)]
            for old, new in zip(before[:2], after[:2]):
                np.testing.assert_array_equal(new[:, keep], old[:, keep])
            for old, new in zip(before[2:], after[2:]):
                np.testing.assert_array_equal(new[live], old[live])

        one = self._serve(self._engine(cfg, params, self.SPEC, 1,
                                       monkeypatch), prompts, untouched)
        assert one == every == dense

    @pytest.mark.parametrize("rows", [1, 2, 3])
    def test_spans_count_the_dispatched_rows(self, small_model, monkeypatch,
                                             rows):
        from repro.obs import Telemetry

        cfg, params = small_model
        obs = Telemetry(tracing=True)
        eng = self._engine(cfg, params, self.SPEC, rows, monkeypatch, obs)
        self._serve(eng, self._prompts(cfg))
        spans = [s for s in obs.tracer.spans
                 if s.name == "serve.admit.prefill"]
        assert spans and all(s.args["rows"] == rows
                             and s.args["width"] == rows * 8
                             for s in spans)
        assert eng.prefill_flops_proxy == sum(s.args["width"]
                                              for s in spans)
        # every prompt token is prefilled once
        assert sum(s.args["tokens"] for s in spans) == sum(
            len(p) for p in self._prompts(cfg))

    def test_admit_compiles_once(self, small_model, monkeypatch):
        cfg, params = small_model
        spec = dataclasses.replace(self.SPEC, max_len=72)
        eng = self._engine(cfg, params, spec, 1, monkeypatch)
        sizes = []

        def admit(n, start):
            for i in range(n):
                eng.submit(np.arange(start + i, start + i + 10) %
                           cfg.vocab_size, max_new_tokens=2)
            eng._admit()
            sizes.append(eng._admit_fn._cache_size())
            eng.run()

        admit(1, 0)
        admit(2, 3)
        admit(3, 7)
        assert sizes == [1, 1, 1]
