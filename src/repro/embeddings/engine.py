"""Sharded embedding engine — the SparseCore execution model in JAX (§3.5).

The SC places embedding tables anywhere in the machine's collective HBM and
moves (deduplicated) ids to row owners and vectors back with variable-length
all-to-alls over ICI.  This engine reproduces that dataflow as a **pipelined
multi-group executor**:

  ids --dedup--> unique ids --all-to-all--> row owners --gather (Pallas)-->
  vectors --all-to-all--> requesters --segment combine--> dense activations

Fused descriptor layout
-----------------------
Locally-resident tables (every table on one device; the replicated set under
sharding) are no longer looked up one launch per table.  All of them are
viewed as ONE row space: the concatenation of each width-group's rows, lanes
padded to the widest dim, addressed by a *descriptor stream* —

    rows  (B, S) : absolute fused row id per (sample, descriptor column),
                   i.e. ``group_offset + table_offset + feature id``
    slots (S,)   : which output slot (table) each descriptor column feeds
    means (K,)   : per-slot combiner flag

— exactly the SC Fetch unit's per-table descriptor list.  One Pallas grid
(``kernels.embedding_lookup.fused_lookup_kernel_call``) then covers every
table, amortising per-launch (CISC instruction issue) overhead across the
whole table batch; the backward is one fused Flush-unit scatter with an
exact ``custom_vjp`` (``kernels.ops.fused_lookup``).

Pipelined distributed dataflow
------------------------------
Two distributed modes share the row-sharded storage:
  * ``a2a``  — the paper-faithful path above (ids sharded over the model axis).
  * ``psum`` — ids replicated over the model axis; each shard partially
    combines its local rows and the partials are psum-merged.  Cheaper for
    small valency, used as an auto fallback and as a §Perf comparison point.

With ``ctx.emb_pipeline`` (default) all width-groups of a mode run inside a
single ``shard_map`` and are software-pipelined (``parallel.overlap.
software_pipeline``): group k+1's id all-to-all is issued before group k's
owner-gather + vector all-to-all + combine consumes its buffers, so the
exchanges ride under the previous group's compute instead of serialising.

Hot-id cache
------------
An optional per-group LFU cache (``embeddings.cache.HotIdCache``) keeps the
hottest rows replicated on every shard.  Cache hits are served locally and
never enter the all-to-all (the send-capacity can shrink by the cache's
``capacity_scale``); gradients remain exact because the cached lookup is
wrapped in a ``custom_vjp`` whose backward differentiates the *uncached*
dataflow, scattering every gradient back to the authoritative sharded rows.

Tables of the same width are concatenated into one row space ("groups");
table-sharding (paper §3.3) is row-sharding the concatenation with
shard-aligned offsets, so all strategies use one code path.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EmbeddingTableConfig
from repro.embeddings.cache import HotIdCache
from repro.embeddings.dedup import dedup_ids
from repro.embeddings.sharding import Placement, plan_placement
from repro.parallel.context import LOCAL, ParallelContext
from repro.parallel.overlap import software_pipeline

P = jax.sharding.PartitionSpec


# ---------------------------------------------------------------------------
# Group layout
# ---------------------------------------------------------------------------

@dataclass
class TableSlot:
    spec: EmbeddingTableConfig
    offset: int            # row offset inside the group array
    rows: int              # padded rows reserved


@dataclass
class Group:
    dim: int
    slots: List[TableSlot] = field(default_factory=list)
    total_rows: int = 0
    prefix: str = "group"   # "group" = row-sharded, "local" = replicated

    @property
    def name(self) -> str:
        return f"{self.prefix}_d{self.dim}"


@dataclass(frozen=True)
class FusedSlot:
    """One output slot of the fused descriptor stream (= one table)."""
    name: str
    combiner: str
    dim: int
    row_base: int          # absolute row offset in the fused row space
    cols: Tuple[int, int]  # descriptor-column span [a, b)


class EmbeddingCollection:
    """Plans placement and owns the parameter layout for a set of tables.

    With ``fused_storage`` (the pipeline-v2 layout, used by the DLRM stack)
    the locally-resident (replicated) tables are also packed into per-width
    ``local_d{D}`` row spaces — the descriptor-addressed layout the fused
    lookup consumes directly (one native-width gather per width-group, no
    per-table parameters and no per-step re-concatenation).  Sharded
    width-groups keep their own per-dim row spaces either way.
    """

    def __init__(self, tables: Sequence[EmbeddingTableConfig],
                 num_shards: int, *, fused_storage: bool = False):
        self.tables = list(tables)
        self.num_shards = max(1, num_shards)
        self.fused_storage = fused_storage
        self.plan = plan_placement(tables, self.num_shards)
        self.replicated: List[EmbeddingTableConfig] = []
        self.groups: Dict[int, Group] = {}
        # deterministic order: big tables first within each group
        for t in sorted(tables, key=lambda t: -t.vocab_size * t.dim):
            placement = self.plan[t.name]
            if placement.strategy == "replicate":
                self.replicated.append(t)
                continue
            g = self.groups.setdefault(t.dim, Group(dim=t.dim))
            off = g.total_rows
            if placement.strategy == "table":
                # shard-align so the table lands on as few shards as possible
                pass  # alignment applied after all rows known (below)
            rows = t.vocab_size
            g.slots.append(TableSlot(t, off, rows))
            g.total_rows += rows
        # pad every group to a multiple of num_shards
        for g in self.groups.values():
            pad = (-g.total_rows) % self.num_shards
            g.total_rows += pad
        # fused_storage: locally-resident tables pack into per-width
        # "local_d{D}" row spaces (native lane width, no padding waste)
        self.local_groups: Dict[int, Group] = {}
        if fused_storage:
            for t in self.replicated:
                g = self.local_groups.setdefault(
                    t.dim, Group(dim=t.dim, prefix="local"))
                g.slots.append(TableSlot(t, g.total_rows, t.vocab_size))
                g.total_rows += t.vocab_size

    # -- params -------------------------------------------------------------

    def init(self, key) -> Dict[str, Any]:
        """Initialise all embedding tables: one fused ``local_d{D}`` row
        space per width-group under fused storage, per-table arrays
        otherwise.  Returns the params dict consumed by `lookup`."""
        params: Dict[str, Any] = {}
        keys = jax.random.split(key, len(self.groups) + len(self.replicated))
        i = 0
        for dim, g in sorted(self.groups.items()):
            params[g.name] = (jax.random.normal(
                keys[i], (g.total_rows, dim), jnp.float32) * 0.01)
            i += 1
        rep: Dict[str, jax.Array] = {}
        for t in self.replicated:
            rep[t.name] = (jax.random.normal(
                keys[i], (t.vocab_size, t.dim), jnp.float32) * 0.01)
            i += 1
        if self.fused_storage:
            for dim, g in sorted(self.local_groups.items()):
                params[g.name] = jnp.concatenate(
                    [rep[s.spec.name] for s in g.slots], axis=0)
        else:
            params.update(rep)
        return params

    def param_specs(self, ctx: ParallelContext) -> Dict[str, Any]:
        """PartitionSpecs matching init()'s pytree."""
        specs: Dict[str, Any] = {}
        for dim, g in sorted(self.groups.items()):
            specs[g.name] = ctx.spec(ctx.model_axis, None)
        if self.fused_storage:
            for dim, g in sorted(self.local_groups.items()):
                specs[g.name] = ctx.spec(None, None)
        else:
            for t in self.replicated:
                specs[t.name] = ctx.spec(None, None)
        return specs

    def table_view(self, params, t: EmbeddingTableConfig) -> jax.Array:
        """Per-table (V, D) view of wherever the table's rows live."""
        if self.fused_storage and t.dim in self.local_groups:
            g = self.local_groups[t.dim]
            for s in g.slots:
                if s.spec.name == t.name:
                    return params[g.name][s.offset: s.offset + s.rows]
        return params[t.name]

    def _local_units(self, params) -> List[Tuple[Group, jax.Array]]:
        """(width-group, its full row-space array) for the local set."""
        if self.fused_storage:
            return [(g, params[g.name])
                    for dim, g in sorted(self.local_groups.items())]
        units = []
        for t in self.replicated:
            g = Group(dim=t.dim, prefix="local")
            g.slots.append(TableSlot(t, 0, t.vocab_size))
            g.total_rows = t.vocab_size
            units.append((g, params[t.name]))
        return units

    # -- fused descriptor layout --------------------------------------------

    def fused_entries(self, which: str = "all"
                      ) -> Tuple[List[Tuple[str, str, int, int]], int]:
        """(name, combiner, dim, row_base) per table + fused row count.

        Row bases follow ``fused_table``'s concatenation order: local width-
        groups (or bare replicated tables) sorted by dim, then the sharded
        width-groups.  ``which``: "all" (every table — the full fused row
        space) or "replicated" (only the locally-resident set).
        """
        entries: List[Tuple[str, str, int, int]] = []
        base = 0
        if self.fused_storage:
            for dim, g in sorted(self.local_groups.items()):
                for s in g.slots:
                    entries.append((s.spec.name, s.spec.combiner, dim,
                                    base + s.offset))
                base += g.total_rows
        else:
            for t in self.replicated:
                entries.append((t.name, t.combiner, t.dim, base))
                base += t.vocab_size
        if which == "all":
            for dim, g in sorted(self.groups.items()):
                for s in g.slots:
                    entries.append((s.spec.name, s.spec.combiner, dim,
                                    base + s.offset))
                base += g.total_rows
        return entries, base

    def fused_table(self, params, which: str = "all") -> jax.Array:
        """The selected storage as one (R, Dmax) row space — the single-
        grid view the Pallas descriptor kernel consumes."""
        dims = [t.dim for t in self.replicated]
        if which == "all":
            dims += list(self.groups)
        dmax = max(dims)
        parts = []
        if self.fused_storage:
            parts.extend(self._pad_lanes(params[g.name], dmax)
                         for dim, g in sorted(self.local_groups.items()))
        else:
            parts.extend(self._pad_lanes(params[t.name], dmax)
                         for t in self.replicated)
        if which == "all":
            for dim, g in sorted(self.groups.items()):
                parts.append(self._pad_lanes(params[g.name], dmax))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)

    @staticmethod
    def _pad_lanes(arr, dmax: int):
        if arr.shape[1] == dmax:
            return arr
        return jnp.pad(arr, ((0, 0), (0, dmax - arr.shape[1])))

    def _fused_plan(self, features, which: str = "all"
                    ) -> Tuple[List[FusedSlot], jax.Array, jax.Array]:
        """(slots, desc slot stream (S,), mean flags (K,)) for ``features``.

        Slots are ordered by valency (descriptor-span width) so that
        same-valency tables sit in contiguous descriptor runs — the combine
        then collapses each valency class into ONE reshaped masked-sum.
        """
        entries, _ = self.fused_entries(which)
        entries = sorted(entries,
                         key=lambda e: features[e[0]].shape[1])
        fslots: List[FusedSlot] = []
        c0 = 0
        for name, comb, dim, base in entries:
            vl = features[name].shape[1]
            fslots.append(FusedSlot(name, comb, dim, base, (c0, c0 + vl)))
            c0 += vl
        widths = [s.cols[1] - s.cols[0] for s in fslots]
        slots = jnp.asarray(np.repeat(np.arange(len(fslots)), widths),
                            jnp.int32)
        means = jnp.asarray([s.combiner == "mean" for s in fslots], jnp.int32)
        return fslots, slots, means

    def _lookup_fused(self, params, features, *, which: str = "all",
                      use_kernel: bool = False) -> Dict[str, jax.Array]:
        """One descriptor-stream launch over every selected table."""
        if use_kernel:
            # Pallas: the single-grid Fetch-unit model — one launch over
            # the whole padded fused row space
            fslots, slots, means = self._fused_plan(features, which)
            if not fslots:
                return {}
            table = self.fused_table(params, which)
            parts = [jnp.where(features[s.name] >= 0,
                               features[s.name] + s.row_base, -1)
                     for s in fslots]
            rows = (parts[0] if len(parts) == 1
                    else jnp.concatenate(parts, axis=1))
            from repro.kernels import ops as KOPS
            out3 = KOPS.fused_lookup(table, rows, slots, means)
            return {s.name: out3[:, i, :s.dim]
                    for i, s in enumerate(fslots)}
        # XLA: one program, one native-width gather per width-group, one
        # masked reshape-sum per valency class within it
        units = self._local_units(params)
        if which == "all":
            units += [(g, params[g.name])
                      for dim, g in sorted(self.groups.items())]
        out: Dict[str, jax.Array] = {}
        for g, arr in units:
            out.update(_group_fused_lookup(arr, g, features))
        return out

    # -- lookup ---------------------------------------------------------------

    def lookup(self, params, features: Dict[str, jax.Array],
               ctx: ParallelContext = LOCAL, *, method: str = "auto",
               use_kernel: bool = False, fused: Optional[bool] = None,
               cache: Optional[Any] = None) -> Dict[str, jax.Array]:
        """features: name -> (B, max_valency) int32 ids, -1 padded.

        Returns name -> (B, dim) combined embeddings.  ``fused=None`` follows
        ``ctx.emb_pipeline``; ``cache`` is a ``HotIdCache`` (or its
        ``arrays()`` dict) consulted by the distributed a2a path.
        """
        if method == "auto" and ctx.emb_method != "auto":
            method = ctx.emb_method
        if fused is None:
            fused = ctx.emb_pipeline
        cache_arrays = (cache.arrays() if isinstance(cache, HotIdCache)
                        else (cache or {}))
        cache_scale = (cache.capacity_scale
                       if isinstance(cache, HotIdCache) else 1.0)
        ms = ctx.model_axis_size
        local_only = ms <= 1 or not ctx.has_mesh or method == "local"

        out: Dict[str, jax.Array] = {}
        if local_only:
            if fused and (self.replicated or self.groups):
                return self._lookup_fused(params, features, which="all",
                                          use_kernel=use_kernel)
            out.update(self._lookup_replicated_legacy(params, features,
                                                      use_kernel))
            for dim, g in sorted(self.groups.items()):
                ids_all, cols = self._concat_group_ids(g, features)
                rows = _gather_rows(params[g.name], ids_all, use_kernel)
                for name, a, b, combiner in cols:
                    out[name] = _combine(rows[:, a:b], ids_all[:, a:b],
                                         combiner)
            return out

        # locally-resident tables: fused single launch (or legacy per-table)
        if fused and self.replicated:
            out.update(self._lookup_fused(params, features,
                                          which="replicated",
                                          use_kernel=use_kernel))
        else:
            out.update(self._lookup_replicated_legacy(params, features,
                                                      use_kernel))

        # sharded width-groups: resolve the exchange mode per group, then run
        # each mode's groups through one pipelined shard_map
        psum_set: List[Tuple[Group, jax.Array, List]] = []
        a2a_set: List[Tuple[Group, jax.Array, List]] = []
        for dim, g in sorted(self.groups.items()):
            ids_all, cols = self._concat_group_ids(g, features)
            if method == "psum" or (method == "auto"
                                    and ids_all.shape[1] <= 4):
                psum_set.append((g, ids_all, cols))
            else:
                a2a_set.append((g, ids_all, cols))

        if psum_set:
            if fused:
                combined = _rowsharded_psum_multi(
                    tuple(params[g.name] for g, _, _ in psum_set),
                    tuple(i for _, i, _ in psum_set), ctx,
                    cols_list=[c for _, _, c in psum_set])
            else:
                combined = [_rowsharded_psum(params[g.name], ids, ctx,
                                             cols=cols)
                            for g, ids, cols in psum_set]
            for (g, ids, cols), comb in zip(psum_set, combined):
                out.update({name: comb[:, i]
                            for i, (name, a, b, c) in enumerate(cols)})
        if a2a_set:
            caches = [cache_arrays.get(g.name) for g, _, _ in a2a_set]
            if fused:
                combined = _rowsharded_a2a_pipelined(
                    tuple(params[g.name] for g, _, _ in a2a_set),
                    tuple(i for _, i, _ in a2a_set), ctx,
                    cols_list=[c for _, _, c in a2a_set],
                    capacity_factor=ctx.emb_capacity_factor,
                    caches=caches, cache_scale=cache_scale)
            else:
                combined = [_rowsharded_a2a(params[g.name], ids, ctx,
                                            cols=cols,
                                            capacity_factor=
                                            ctx.emb_capacity_factor)
                            for g, ids, cols in a2a_set]
            for (g, ids, cols), comb in zip(a2a_set, combined):
                out.update({name: comb[:, i]
                            for i, (name, a, b, c) in enumerate(cols)})
        return out

    def _lookup_replicated_legacy(self, params, features,
                                  use_kernel: bool) -> Dict[str, jax.Array]:
        """Pre-v2 dataflow: one gather+combine per locally-resident table."""
        return {t.name: _combine(
            _gather_rows(self.table_view(params, t), features[t.name],
                         use_kernel),
            features[t.name], t.combiner) for t in self.replicated}

    @staticmethod
    def _concat_group_ids(g: Group, features):
        """Concat a group's feature ids with row offsets; remember spans."""
        cols: List[Tuple[str, int, int, str]] = []
        parts = []
        c0 = 0
        for s in g.slots:
            ids = features[s.spec.name]
            parts.append(jnp.where(ids >= 0, ids + s.offset, -1))
            cols.append((s.spec.name, c0, c0 + ids.shape[1], s.spec.combiner))
            c0 += ids.shape[1]
        return jnp.concatenate(parts, axis=1), cols


# ---------------------------------------------------------------------------
# Pipelined executor facade
# ---------------------------------------------------------------------------

class PipelinedEmbeddingExecutor:
    """EmbeddingCollection + hot-id cache + per-step LFU bookkeeping.

    The stateless ``coll.lookup`` stays jit-friendly; this facade owns the
    host-side loop around it: observe the step's ids into the LFU, refresh
    the replicated hot rows every ``refresh_every`` steps, and thread the
    cache arrays into the lookup as arguments (never closures, so refreshes
    do not recompile).
    """

    def __init__(self, coll: EmbeddingCollection, *,
                 cache: Optional[HotIdCache] = None,
                 refresh_every: int = 1, method: str = "auto",
                 use_kernel: bool = False):
        self.coll = coll
        self.cache = cache
        self.refresh_every = max(1, refresh_every)
        self.method = method
        self.use_kernel = use_kernel
        self._step = 0

    def observe(self, features) -> None:
        """Fold one step's feature ids into the LFU counts (host-side).

        Only groups the engine will route through the a2a exchange are
        tracked — psum-routed (small-valency) groups never consult the
        cache, so counting them would skew hit_rate and waste snapshots.
        """
        if self.cache is None:
            return
        for dim, g in sorted(self.coll.groups.items()):
            vl = sum(features[s.spec.name].shape[1] for s in g.slots)
            if self.method in ("psum", "local") or (self.method == "auto"
                                                    and vl <= 4):
                continue
            for s in g.slots:
                ids = np.asarray(features[s.spec.name])
                ids = np.where(ids >= 0, ids + s.offset, -1)
                self.cache.observe(g.name, ids)

    def step(self, params, features) -> None:
        """Per-step bookkeeping: observe + periodic refresh."""
        self.observe(features)
        self._step += 1
        if self.cache is not None and self._step % self.refresh_every == 0:
            self.cache.refresh_all(self.coll, params)

    def lookup(self, params, features, ctx: ParallelContext = LOCAL
               ) -> Dict[str, jax.Array]:
        """Pipelined fused multi-group lookup: name -> (B, dim) combined
        embeddings (see `EmbeddingCollection.lookup`; this executor pins
        ``fused=True`` and threads its hot-id cache through)."""
        return self.coll.lookup(params, features, ctx, method=self.method,
                                use_kernel=self.use_kernel, fused=True,
                                cache=self.cache)


# ---------------------------------------------------------------------------
# Local gather + combine
# ---------------------------------------------------------------------------

def _group_fused_lookup(arr, g: Group, features) -> Dict[str, jax.Array]:
    """Descriptor-stream lookup over ONE width-group's (R, D) row space.

    Slots are ordered by valency so same-valency tables occupy contiguous
    equal-width descriptor runs; each run-class combines as a single
    (B, nw, W, D) masked reduction — the XLA shape of the fused grid.
    """
    slots = sorted(g.slots, key=lambda s: features[s.spec.name].shape[1])
    parts = [jnp.where(features[s.spec.name] >= 0,
                       features[s.spec.name] + s.offset, -1) for s in slots]
    rows = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    B = rows.shape[0]
    D = arr.shape[1]
    valid = rows >= 0
    # mode="clip" routes the -1 invalids to row 0; the mask zeroes them
    vecs = jnp.take(arr, rows, axis=0, mode="clip")           # (B, S, D)
    out: Dict[str, jax.Array] = {}
    i = c0 = 0
    while i < len(slots):
        w = features[slots[i].spec.name].shape[1]
        j = i
        while j < len(slots) and \
                features[slots[j].spec.name].shape[1] == w:
            j += 1
        cls = slots[i:j]
        nw = len(cls)
        a, b = c0, c0 + nw * w
        block = vecs[:, a:b].reshape(B, nw, w, D)
        vmask = valid[:, a:b].reshape(B, nw, w).astype(vecs.dtype)
        seg = (block * vmask[..., None]).sum(axis=2)          # (B, nw, D)
        cnt = vmask.sum(axis=2)
        is_mean = jnp.asarray([s.spec.combiner == "mean" for s in cls])
        denom = jnp.where(is_mean[None, :], jnp.maximum(cnt, 1.0), 1.0)
        seg = seg / denom[..., None]
        for k, s in enumerate(cls):
            out[s.spec.name] = seg[:, k]
        i, c0 = j, b
    return out


def _gather_rows(table, ids, use_kernel: bool = False):
    """(V, D), (B, Vl) -> (B, Vl, D); invalid ids give zero rows."""
    if use_kernel:
        from repro.kernels import ops as KOPS
        return KOPS.embedding_gather(table, ids)
    valid = (ids >= 0)[..., None]
    rows = jnp.take(table, ids, axis=0, mode="clip")
    return jnp.where(valid, rows, 0.0)


def _combine(rows, ids, combiner: str):
    """(B, Vl, D), (B, Vl) -> (B, D)."""
    valid = (ids >= 0).astype(rows.dtype)
    out = (rows * valid[..., None]).sum(axis=1)
    if combiner == "mean":
        out = out / jnp.maximum(valid.sum(axis=1), 1.0)[..., None]
    return out


# ---------------------------------------------------------------------------
# Distributed row-sharded lookups
# ---------------------------------------------------------------------------

def _segment_combine(rows, ids, cols):
    """(B, Vg, D) rows -> (B, K, D) per-table combined vectors (local op)."""
    B, Vg, D = rows.shape
    K = len(cols)
    sel = np.zeros((Vg, K), np.float32)
    for i, (name, a, b, comb) in enumerate(cols):
        sel[a:b, i] = 1.0
    sel = jnp.asarray(sel)
    valid = (ids >= 0).astype(rows.dtype)
    # a TPU's default f32 matmul rounds its operands to bf16; the 0/1
    # selector sum must stay exact, as the per-table combine is
    out = jnp.einsum("bvd,vk->bkd", rows * valid[..., None], sel,
                     precision=jax.lax.Precision.HIGHEST)
    counts = jnp.einsum("bv,vk->bk", valid, sel)
    means = jnp.asarray([c == "mean" for *_, c in cols])
    denom = jnp.where(means[None, :], jnp.maximum(counts, 1.0), 1.0)
    return out / denom[..., None]


def _rowsharded_psum(table, ids, ctx: ParallelContext, *, cols):
    """ids replicated over the model axis; shards partially gather, combine
    locally to (B, K, D), and psum the combined vectors."""
    axis = ctx.model_axis
    ms = ctx.model_axis_size
    bspec = (ctx.batch_axes or None) if ctx.has_mesh else None
    V = table.shape[0]
    rps = V // ms

    def local(table_loc, ids_loc):
        combined = _psum_partial(table_loc, ids_loc, axis, rps, cols, ctx)
        return jax.lax.psum(combined, axis)

    fn = jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(axis, None), P(bspec, None)),
        out_specs=P(bspec, None, None), check_vma=False)
    return fn(table, ids)


def _psum_partial(table_loc, ids_loc, axis, rps, cols, ctx):
    """The shard-local compute half of the psum mode."""
    base = jax.lax.axis_index(axis) * rps
    lid = ids_loc - base
    ok = (ids_loc >= 0) & (lid >= 0) & (lid < rps)
    rows = jnp.take(table_loc, lid, axis=0, mode="clip")
    rows = jnp.where(ok[..., None], rows, 0.0)
    combined = _segment_combine(rows, ids_loc, cols)
    if ctx.emb_wire_bf16:
        combined = combined.astype(jnp.bfloat16)  # §Perf: half traffic
    return combined


def _rowsharded_psum_multi(tables, ids_list, ctx: ParallelContext, *,
                           cols_list):
    """All psum-mode width-groups in ONE shard_map, software-pipelined:
    group k+1's local gather+combine is issued before group k's psum, so
    the reduction rides under the next group's compute."""
    axis = ctx.model_axis
    ms = ctx.model_axis_size
    bspec = (ctx.batch_axes or None) if ctx.has_mesh else None
    n = len(tables)
    rps = [t.shape[0] // ms for t in tables]

    def local(tabs, idss):
        def stage_a(k):          # compute: shard-local partial combine
            return _psum_partial(tabs[k], idss[k], axis, rps[k],
                                 cols_list[k], ctx)

        def stage_b(partial, k):  # communicate: merge partials
            return jax.lax.psum(partial, axis)

        return tuple(software_pipeline(stage_a, stage_b, range(n)))

    fn = jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(tuple(P(axis, None) for _ in range(n)),
                  tuple(P(bspec, None) for _ in range(n))),
        out_specs=tuple(P(bspec, None, None) for _ in range(n)),
        check_vma=False)
    return list(fn(tuple(tables), tuple(ids_list)))


def _a2a_descriptors(ids_loc, ms: int, rps: int, C: int, cache):
    """Dedup one group's shard-local ids and lay out the send descriptors.

    Returns (send_ids (ms, C), slot (N,), keep (N,), inv (N,), hit (N,),
    cpos (N,)): the id all-to-all payload plus everything the consume stage
    needs to reassemble per-occurrence vectors.  Cache hits are routed to
    the drop bucket — they never enter the exchange.
    """
    N = ids_loc.size
    flat = ids_loc.reshape(N)
    uids, inv, num = dedup_ids(flat)                 # sorted, -1 tail
    valid_u = uids >= 0
    if cache is not None:
        cids, _ = cache
        cpos = jnp.clip(jnp.searchsorted(cids, uids), 0, cids.shape[0] - 1)
        hit = valid_u & (cids[cpos] == uids)
    else:
        cpos = jnp.zeros((N,), jnp.int32)
        hit = jnp.zeros((N,), bool)
    want = valid_u & jnp.logical_not(hit)
    # uids sorted => dest monotonic over the wanted subsequence; rank within
    # each destination = wanted-before-me minus wanted-before-my-bucket
    full_dest = jnp.where(valid_u, uids // rps, ms)
    dest = jnp.where(want, full_dest, ms)            # ms = drop bucket
    wanted = want.astype(jnp.int32)
    cum = jnp.cumsum(wanted) - wanted                # exclusive prefix count
    cum_ext = jnp.concatenate([cum, jnp.sum(wanted)[None]])
    starts = jnp.searchsorted(full_dest, jnp.arange(ms), side="left")
    before = cum_ext[starts]                         # wanted with dest < d
    rank = cum - before[jnp.clip(dest, 0, ms - 1)]
    keep = want & (rank < C)
    slot = jnp.where(keep, dest * C + rank, ms * C)
    send_ids = jnp.full((ms * C + 1,), -1, jnp.int32).at[slot].set(
        uids, mode="drop")[:-1]
    return send_ids.reshape(ms, C), slot, keep, inv, hit, cpos


def _a2a_consume(table_loc, desc, ids_loc, cols, ctx, axis, rps: int, cache):
    """Owner-side gather + vector all-to-all + reassembly + combine."""
    recv_ids, slot, keep, inv, hit, cpos = desc
    Bl, Vl = ids_loc.shape
    ms, C = recv_ids.shape
    D = table_loc.shape[1]
    base = jax.lax.axis_index(axis) * rps
    lid = recv_ids - base
    ok = (recv_ids >= 0) & (lid >= 0) & (lid < rps)
    rows = jnp.take(table_loc, lid, axis=0, mode="clip")
    rows = jnp.where(ok[..., None], rows, 0.0)       # (ms, C, D)
    if ctx.emb_wire_bf16:
        rows = rows.astype(jnp.bfloat16)   # §Perf: halve vector traffic
    vecs = jax.lax.all_to_all(rows, axis, 0, 0)      # (ms, C, D) back
    vflat = jnp.concatenate(
        [vecs.reshape(ms * C, D), jnp.zeros((1, D), vecs.dtype)], 0)
    uvecs = vflat[slot] * keep[:, None].astype(vflat.dtype)
    if cache is not None:
        _, crows = cache
        hot = crows[cpos].astype(uvecs.dtype)        # replicated hot rows
        uvecs = jnp.where(hit[:, None], hot, uvecs)
    occ = uvecs[inv]                                 # broadcast to ids
    return _segment_combine(occ.reshape(Bl, Vl, D), ids_loc, cols)


def _a2a_capacity(ids, ms: int, capacity_factor: float,
                  scale: float = 1.0) -> int:
    N = ids.shape[0] * ids.shape[1]
    return max(8, int(math.ceil(N / ms * capacity_factor * scale)))


def _rowsharded_a2a(table, ids, ctx: ParallelContext, *, cols,
                    capacity_factor: float = 2.0):
    """The paper-faithful SparseCore path for ONE width-group: dedup → id
    all-to-all → owner gather → vector all-to-all → per-occurrence broadcast
    → LOCAL combine.

    ids: (B, Vl) with B sharded over (batch_axes, model) — the sparse stage
    splits the batch over the model axis too, exactly like SC's per-chip
    sample ownership.  Output (B, K, D) combined vectors (only those cross
    shard boundaries on the way back to the dense stack).
    """
    return _rowsharded_a2a_pipelined(
        (table,), (ids,), ctx, cols_list=[cols],
        capacity_factor=capacity_factor, caches=[None])[0]


def _rowsharded_a2a_pipelined(tables, ids_list, ctx: ParallelContext, *,
                              cols_list, capacity_factor: float = 2.0,
                              caches=None, cache_scale: float = 1.0):
    """All a2a-mode width-groups in ONE shard_map, double-buffered: group
    k+1's descriptor build + id all-to-all overlaps group k's gather +
    vector all-to-all + combine (``software_pipeline``)."""
    axis = ctx.model_axis
    ms = ctx.model_axis_size
    bspec = (ctx.batch_axes or None) if ctx.has_mesh else None
    batch_both = tuple([*(ctx.batch_axes or ()), axis])
    n = len(tables)
    caches = list(caches) if caches is not None else [None] * n
    rps = [t.shape[0] // ms for t in tables]
    cache_args = tuple(c for c in caches if c is not None)
    cache_slots = [i for i, c in enumerate(caches) if c is not None]

    def make_run(with_cache: bool):
        # the cached forward provisions miss-only exchange buffers
        # (capacity * cache_scale); the uncached dataflow — also the exact
        # backward — keeps full capacity so no gradient is ever dropped
        caps = [_a2a_capacity(
            ids, ms, capacity_factor,
            cache_scale if (with_cache and caches[k] is not None) else 1.0)
            for k, ids in enumerate(ids_list)]

        def local(tabs, idss, cargs):
            cmap = ({k: cargs[j] for j, k in enumerate(cache_slots)}
                    if with_cache else {})

            def stage_a(k):          # descriptor build + id exchange
                send, slot, keep, inv, hit, cpos = _a2a_descriptors(
                    idss[k], ms, rps[k], caps[k], cmap.get(k))
                recv = jax.lax.all_to_all(send, axis, 0, 0)
                return recv, slot, keep, inv, hit, cpos

            def stage_b(desc, k):    # gather + vector exchange + combine
                return _a2a_consume(tabs[k], desc, idss[k], cols_list[k],
                                    ctx, axis, rps[k], cmap.get(k))

            return tuple(software_pipeline(stage_a, stage_b, range(n)))

        cache_specs = (tuple((P(None), P(None, None)) for _ in cache_args)
                       if with_cache else ())
        fn = jax.shard_map(
            local, mesh=ctx.mesh,
            in_specs=(tuple(P(axis, None) for _ in range(n)),
                      tuple(P(batch_both, None) for _ in range(n)),
                      cache_specs),
            out_specs=tuple(P(batch_both, None, None) for _ in range(n)),
            check_vma=False)

        def run(tabs, idss, cargs):
            # reshard batch over (data, model) for the sparse stage, back
            idss = tuple(
                jax.lax.with_sharding_constraint(
                    i, jax.sharding.NamedSharding(ctx.mesh,
                                                  P(batch_both, None)))
                for i in idss)
            outs = fn(tabs, idss, cargs)
            return tuple(
                jax.lax.with_sharding_constraint(
                    o, jax.sharding.NamedSharding(ctx.mesh,
                                                  P(bspec, None, None)))
                for o in outs)
        return run

    run_plain = make_run(False)
    if not cache_args:
        return list(run_plain(tuple(tables), tuple(ids_list), ()))
    return list(_cached_vjp(make_run(True), run_plain,
                            tuple(tables), tuple(ids_list), cache_args))


def _cached_vjp(run_cached, run_plain, tables, ids_list, cache_args):
    """Exact-gradient wrapper for the cached forward.

    The forward serves hits from the (possibly slightly stale) replicated
    cache; the backward differentiates the *uncached* dataflow at the same
    primals, so every gradient is scattered back through the real id/vector
    all-to-all to the authoritative sharded rows.  No gradient ever flows
    into the cache snapshot.
    """
    @jax.custom_vjp
    def cached(tabs, idss, cargs):
        return run_cached(tabs, idss, cargs)

    def fwd(tabs, idss, cargs):
        return run_cached(tabs, idss, cargs), (tabs, idss)

    def bwd(res, g):
        tabs, idss = res
        _, vjp = jax.vjp(lambda tt: run_plain(tt, idss, ()), tabs)
        (dt,) = vjp(g)
        return dt, None, None

    cached.defvjp(fwd, bwd)
    return cached(tables, ids_list, cache_args)


# ---------------------------------------------------------------------------
# Reference (oracle for tests)
# ---------------------------------------------------------------------------

def materialize_tables(coll: EmbeddingCollection, params
                       ) -> Dict[str, jax.Array]:
    """Slice the grouped storage back into per-table (V, D) arrays."""
    out = {}
    for t in coll.replicated:
        out[t.name] = coll.table_view(params, t)
    for dim, g in sorted(coll.groups.items()):
        arr = params[g.name]
        for s in g.slots:
            out[s.spec.name] = arr[s.offset: s.offset + s.spec.vocab_size]
    return out


def lookup_reference(tables: Dict[str, jax.Array],
                     specs: Sequence[EmbeddingTableConfig],
                     features: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    out = {}
    for t in specs:
        rows = _gather_rows(tables[t.name], features[t.name])
        out[t.name] = _combine(rows, features[t.name], t.combiner)
    return out
