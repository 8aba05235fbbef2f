"""Paged decode attention: Pallas kernel (interpret) vs the dense XLA
reference, across seq_lens / GQA / softcap / window — plus the dispatcher
policy (interpret auto-detect, impl selection) the serve fast path relies
on."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import (
    paged_decode_attention_kernel_call, resolve_interpret)
from repro.kernels.flash_attention import flash_attention


def _qkv(key, B, H, KH, S, d, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, d)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KH, d)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KH, d)).astype(dtype)
    return q, k, v


class TestPagedDecodeKernel:
    @pytest.mark.parametrize("B,H,KH,S,d", [
        (1, 2, 2, 32, 16),           # MHA
        (2, 4, 2, 64, 32),           # GQA 2:1
        (3, 8, 1, 48, 8),            # MQA, non-pow2 batch
        (2, 4, 4, 40, 64),           # S not a multiple of bk (pad path)
    ])
    @pytest.mark.parametrize("kw", [
        dict(),
        dict(window=16),
        dict(softcap=30.0),
        dict(window=8, softcap=10.0),
    ])
    def test_matches_ref(self, B, H, KH, S, d, kw):
        key = jax.random.PRNGKey(B * S + H)
        q, k, v = _qkv(key, B, H, KH, S, d)
        lens = jax.random.randint(jax.random.fold_in(key, 7), (B,), 1, S + 1,
                                  jnp.int32)
        got = paged_decode_attention_kernel_call(q, k, v, lens, bk=16,
                                                 interpret=True, **kw)
        want = ref.paged_decode_attention_ref(q, k, v, lens, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_heterogeneous_lens_isolated_per_slot(self):
        """Each slot must see ONLY its own valid prefix: computing a slot
        alone (len rows, batch of 1) equals computing it in the mixed
        batch."""
        key = jax.random.PRNGKey(0)
        B, H, KH, S, d = 4, 4, 2, 64, 16
        q, k, v = _qkv(key, B, H, KH, S, d)
        lens = jnp.asarray([1, 17, 40, 64], jnp.int32)
        batched = paged_decode_attention_kernel_call(q, k, v, lens, bk=16,
                                                     interpret=True)
        for b in range(B):
            solo = ref.paged_decode_attention_ref(
                q[b:b + 1], k[b:b + 1], v[b:b + 1], lens[b:b + 1])
            np.testing.assert_allclose(np.asarray(batched[b]),
                                       np.asarray(solo[0]),
                                       rtol=2e-3, atol=2e-3)

    def test_rows_past_seq_len_ignored(self):
        """Garbage in the cache tail (stale rows of retired requests) must
        not leak into the output."""
        key = jax.random.PRNGKey(3)
        B, H, KH, S, d = 2, 2, 2, 32, 8
        q, k, v = _qkv(key, B, H, KH, S, d)
        lens = jnp.asarray([10, 20], jnp.int32)
        out1 = paged_decode_attention_kernel_call(q, k, v, lens, bk=8,
                                                  interpret=True)
        mask = (jnp.arange(S)[None, :, None, None]
                >= lens[:, None, None, None])
        k2 = jnp.where(mask, 1e9, k)
        v2 = jnp.where(mask, -1e9, v)
        out2 = paged_decode_attention_kernel_call(q, k2, v2, lens, bk=8,
                                                  interpret=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-6, atol=1e-6)

    def test_zero_len_slot_returns_zeros(self):
        key = jax.random.PRNGKey(5)
        q, k, v = _qkv(key, 2, 2, 2, 16, 8)
        lens = jnp.asarray([0, 16], jnp.int32)
        out = paged_decode_attention_kernel_call(q, k, v, lens, bk=8,
                                                 interpret=True)
        np.testing.assert_allclose(np.asarray(out[0]), 0.0)
        assert np.abs(np.asarray(out[1])).sum() > 0

    def test_block_size_independence(self):
        key = jax.random.PRNGKey(11)
        q, k, v = _qkv(key, 2, 4, 2, 64, 16)
        lens = jnp.asarray([13, 57], jnp.int32)
        outs = [paged_decode_attention_kernel_call(q, k, v, lens, bk=bk,
                                                   interpret=True)
                for bk in (8, 16, 64)]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                       rtol=1e-5, atol=1e-5)

    def test_bf16(self):
        key = jax.random.PRNGKey(7)
        q, k, v = _qkv(key, 2, 2, 2, 32, 16, jnp.bfloat16)
        lens = jnp.asarray([9, 31], jnp.int32)
        got = paged_decode_attention_kernel_call(q, k, v, lens, bk=16,
                                                 interpret=True)
        want = ref.paged_decode_attention_ref(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_matches_dense_decode_semantics(self):
        """At full length the paged ref equals last-row causal flash
        attention — the dense decode it replaces."""
        key = jax.random.PRNGKey(9)
        B, H, KH, S, d = 2, 4, 2, 32, 16
        ks = jax.random.split(key, 3)
        qfull = jax.random.normal(ks[0], (B, H, S, d), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KH, d), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KH, d), jnp.float32)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        dense = ref.flash_attention_ref(qfull, kt, vt, causal=True)
        lens = jnp.full((B,), S, jnp.int32)
        paged = ref.paged_decode_attention_ref(qfull[:, :, -1], k, v, lens)
        np.testing.assert_allclose(np.asarray(paged),
                                   np.asarray(dense[:, :, -1]),
                                   rtol=1e-5, atol=1e-5)


class TestBlockTableKernel:
    """Block-table-indexed variant (pooled prefix-shared KV): the kernel
    reads the SAME logical view the gather-based reference materialises."""

    def _pooled(self, key, B, H, KH, NB, bs, nb, d, dtype=jnp.float32):
        ks = jax.random.split(key, 4)
        q = jax.random.normal(ks[0], (B, H, d)).astype(dtype)
        k = jax.random.normal(ks[1], (NB, bs, KH, d)).astype(dtype)
        v = jax.random.normal(ks[2], (NB, bs, KH, d)).astype(dtype)
        # random permutation tables: slots map disjoint-or-shared physical
        # blocks in arbitrary order, exactly what the pool hands out
        perm = jax.random.permutation(ks[3], NB)[:B * nb]
        tables = perm.reshape(B, nb).astype(jnp.int32)
        return q, k, v, tables

    @pytest.mark.parametrize("kw", [
        dict(),
        dict(window=16),
        dict(softcap=20.0),
    ])
    def test_matches_bt_ref(self, kw):
        from repro.kernels.decode_attention import (
            paged_decode_attention_bt_kernel_call)
        key = jax.random.PRNGKey(21)
        B, H, KH, NB, bs, nb, d = 3, 4, 2, 16, 8, 4, 16
        q, k, v, tables = self._pooled(key, B, H, KH, NB, bs, nb, d)
        lens = jnp.asarray([1, 13, 32], jnp.int32)
        got = paged_decode_attention_bt_kernel_call(
            q, k, v, lens, tables, interpret=True, **kw)
        want = ref.paged_decode_attention_bt_ref(q, k, v, lens, tables, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_shared_block_equals_private_copy(self):
        """Two slots mapping the SAME physical prefix block must read the
        same lanes a private copy would — sharing is invisible to the
        math."""
        key = jax.random.PRNGKey(22)
        B, H, KH, NB, bs, nb, d = 2, 2, 2, 8, 4, 2, 8
        q, k, v, _ = self._pooled(key, B, H, KH, NB, bs, nb, d)
        shared = jnp.asarray([[0, 1], [0, 2]], jnp.int32)   # block 0 shared
        lens = jnp.asarray([6, 6], jnp.int32)
        got = ref.paged_decode_attention_bt_ref(q, k, v, lens, shared)
        # materialise each slot's logical view densely
        for b, tb in enumerate([[0, 1], [0, 2]]):
            kc = jnp.concatenate([k[t] for t in tb])[None]
            vc = jnp.concatenate([v[t] for t in tb])[None]
            solo = ref.paged_decode_attention_ref(
                q[b:b + 1], kc, vc, lens[b:b + 1])
            np.testing.assert_allclose(np.asarray(got[b]),
                                       np.asarray(solo[0]),
                                       rtol=1e-6, atol=1e-6)

    def test_stale_pool_blocks_ignored(self):
        """Unmapped pool blocks and lanes past seq_len may hold garbage
        (retired requests, in-flight prefills) without leaking in."""
        from repro.kernels.decode_attention import (
            paged_decode_attention_bt_kernel_call)
        key = jax.random.PRNGKey(23)
        B, H, KH, NB, bs, nb, d = 2, 2, 2, 8, 4, 2, 8
        q, k, v, _ = self._pooled(key, B, H, KH, NB, bs, nb, d)
        tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        lens = jnp.asarray([5, 7], jnp.int32)
        out1 = paged_decode_attention_bt_kernel_call(q, k, v, lens, tables,
                                                     interpret=True)
        # poison every unmapped block and every lane past each seq_len
        k2, v2 = k.at[4:].set(1e9), v.at[4:].set(-1e9)
        k2 = k2.at[1, 1:].set(1e9)       # slot 0 lanes [5, 8)
        v2 = v2.at[1, 1:].set(-1e9)
        k2 = k2.at[3, 3:].set(1e9)       # slot 1 lane 7
        v2 = v2.at[3, 3:].set(-1e9)
        out2 = paged_decode_attention_bt_kernel_call(q, k2, v2, lens, tables,
                                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("kw", [dict(), dict(window=6, softcap=20.0)],
                             ids=["plain", "window_softcap"])
    def test_layer_flattened_pool_matches_per_layer(self, kw):
        """Pooled decode hands the kernel every layer's pool at once,
        (Ls * NB, bs, KH, d), with each table clamped and then offset by
        l * NB: layer l's answer must be the per-layer call's, bitwise,
        out-of-range entries (unadmitted slots, unused tails) included."""
        from repro.kernels.decode_attention import (
            paged_decode_attention_bt_kernel_call)
        key = jax.random.PRNGKey(25)
        Ls, B, H, KH, NB, bs, nb, d = 3, 3, 4, 2, 10, 8, 3, 16
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, H, d))
        k = jax.random.normal(ks[1], (Ls, NB, bs, KH, d))
        v = jax.random.normal(ks[2], (Ls, NB, bs, KH, d))
        tables = jnp.asarray([[NB] * nb, [4, 1, NB], [9, 0, 7]], jnp.int32)
        lens = jnp.asarray([0, 11, 24], jnp.int32)
        kf = k.reshape(Ls * NB, bs, KH, d)
        vf = v.reshape(Ls * NB, bs, KH, d)
        for l in range(Ls):
            got = paged_decode_attention_bt_kernel_call(
                q, kf, vf, lens, jnp.clip(tables, 0, NB - 1) + l * NB,
                interpret=True, **kw)
            want = paged_decode_attention_bt_kernel_call(
                q, k[l], v[l], lens, tables, interpret=True, **kw)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # slots that are not decoding, as the decode step hands them to the
    # kernel with length 0: first, last, a run of two, every other one
    DEAD = {"first": [0], "last": [5], "consecutive": [2, 3],
            "interleaved": [0, 2, 4]}

    def _dead_inputs(self, dead):
        key = jax.random.PRNGKey(26)
        B, H, KH, NB, bs, nb, d = 6, 4, 2, 32, 8, 4, 16
        q, k, v, tables = self._pooled(key, B, H, KH, NB, bs, nb, d)
        full = jnp.asarray([5, 32, 17, 9, 24, 1], jnp.int32)
        zeroed = full.at[jnp.asarray(dead)].set(0)
        return q, k, v, tables, full, zeroed

    @pytest.mark.parametrize("kw", [dict(), dict(window=6, softcap=20.0)],
                             ids=["plain", "window_softcap"])
    @pytest.mark.parametrize("dead", list(DEAD), ids=list(DEAD))
    def test_dead_slots_leave_live_slots_bitwise(self, dead, kw):
        """A length-0 slot returns zeros and leaves every other slot's
        output bitwise as it is when the slot carries its full length,
        in the kernel and in the XLA reference."""
        from repro.kernels.decode_attention import (
            paged_decode_attention_bt_kernel_call)
        idx = self.DEAD[dead]
        q, k, v, tables, full, zeroed = self._dead_inputs(idx)
        live = np.setdiff1d(np.arange(q.shape[0]), idx)
        for fn in (functools.partial(paged_decode_attention_bt_kernel_call,
                                     interpret=True),
                   ref.paged_decode_attention_bt_ref):
            want = np.asarray(fn(q, k, v, full, tables, **kw))
            got = np.asarray(fn(q, k, v, zeroed, tables, **kw))
            np.testing.assert_array_equal(got[live], want[live])
            np.testing.assert_array_equal(got[idx], 0.0)

    @pytest.mark.parametrize("dead", list(DEAD) + ["all"],
                             ids=list(DEAD) + ["all"])
    def test_dead_slots_rename_the_previous_block(self, dead):
        """`held_block_tables`: the kernel's index map names, at every grid
        step of a length-0 slot, the block the step before it named (the
        first live slot's first block where no step comes before), so the
        pipeline fetches nothing for it; live slots' steps are unchanged."""
        from repro.kernels.decode_attention import (
            _bt_block, held_block_tables)
        B, nb, bs = 6, 4, 8
        idx = list(range(B)) if dead == "all" else self.DEAD[dead]
        _, _, _, tables, _, lens = self._dead_inputs(idx)

        def named(bt):
            return [[int(_bt_block(b, j, lens, bt, bs)) for j in range(nb)]
                    for b in range(B)]

        got = named(held_block_tables(lens, tables, bs))
        want = named(tables)
        steps = [(b, j) for b in range(B) for j in range(nb)]
        first_live = next((want[b][0] for b in range(B) if b not in idx),
                          None)
        for n, (b, j) in enumerate(steps):
            if b not in idx:
                assert got[b][j] == want[b][j]
            elif n:
                pb, pj = steps[n - 1]
                assert got[b][j] == got[pb][pj]
            elif first_live is not None:
                assert got[b][j] == first_live
        # a step fetches where it names another block than the step before:
        # the whole grid fetches what its live slots alone would (one
        # block at least, for the first step)

        def fetches(seq):
            return len(seq[:1]) + sum(a != b for a, b in zip(seq, seq[1:]))

        assert fetches([got[b][j] for b, j in steps]) == max(1, fetches(
            [want[b][j] for b, j in steps if b not in idx]))

    def test_ops_bt_dispatcher(self):
        key = jax.random.PRNGKey(24)
        B, H, KH, NB, bs, nb, d = 2, 4, 2, 16, 8, 4, 16
        q, k, v, tables = self._pooled(key, B, H, KH, NB, bs, nb, d)
        lens = jnp.asarray([9, 27], jnp.int32)
        got = ops.paged_decode_attention_bt(q, k, v, lens, tables,
                                            impl="auto")
        want = ref.paged_decode_attention_bt_ref(q, k, v, lens, tables)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


class TestDispatchPolicy:
    def test_interpret_auto_detect(self):
        """interpret=None resolves by backend: interpret mode off-TPU."""
        assert resolve_interpret(None) == (jax.default_backend() != "tpu")
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False

    def test_flash_attention_interpret_default_auto(self):
        """flash_attention(interpret=None) must run on the host backend
        (auto-selecting interpret mode) and match the oracle."""
        key = jax.random.PRNGKey(1)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (1, 2, 32, 16), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 32, 16), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 32, 16), jnp.float32)
        got = flash_attention(q, k, v, bq=16, bk=16)       # interpret=None
        want = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("impl", ["auto", "xla"])
    def test_ops_dispatcher(self, impl):
        key = jax.random.PRNGKey(2)
        q, k, v = _qkv(key, 2, 4, 2, 32, 16)
        lens = jnp.asarray([5, 29], jnp.int32)
        got = ops.paged_decode_attention(q, k, v, lens, impl=impl)
        want = ref.paged_decode_attention_ref(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    def test_kernel_reachable_from_model_decode(self):
        """The serve decode path must be able to launch the Pallas kernel:
        with a static-window layer grouping, forcing decode_attn="paged"
        runs the kernel in-model (interpret here) and matches the dense
        path's logits bit-for-bit down to kernel tolerance."""
        import dataclasses as dc

        from repro.configs import registry
        from repro.models import api
        from repro.parallel.context import LOCAL

        cfg = registry.get_reduced("olmo-1b")
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                  cfg.vocab_size, jnp.int32)
        _, cache = api.prefill(cfg, params, {"tokens": toks}, max_len=32)
        lens = jnp.full((2,), 8, jnp.int32)
        budget = jnp.full((2,), 2, jnp.int32)
        last = jnp.zeros((2,), jnp.int32)
        outs = {}
        for impl in ("dense", "paged"):
            ctx = dc.replace(LOCAL, decode_attn=impl, decode_kv_block=16)
            t, *_ = api.decode_n(cfg, params, cache, last, lens, budget,
                                 ctx, num_steps=2)
            outs[impl] = np.asarray(t)
        np.testing.assert_array_equal(outs["dense"], outs["paged"])

    def test_dispatcher_traced_window_falls_back_to_xla(self):
        """A traced (per-layer scanned) window must lower through the XLA
        path even when the kernel is forced."""
        key = jax.random.PRNGKey(4)
        q, k, v = _qkv(key, 2, 2, 2, 32, 8)
        lens = jnp.asarray([10, 30], jnp.int32)

        @jax.jit
        def f(win):
            return ops.paged_decode_attention(q, k, v, lens, window=win,
                                              impl="pallas")

        got = f(jnp.asarray(8, jnp.int32))
        want = ref.paged_decode_attention_ref(q, k, v, lens, window=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)
