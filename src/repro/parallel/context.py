"""ParallelContext — the minimal bridge between model code and the mesh.

Model code is pure JAX; the few places that need explicit collectives
(MoE expert-parallel all-to-all, sparse-embedding exchange, flash-decode
merge) read axis names from this context.  ``ctx=None`` (or a context whose
axes are absent/size-1) degenerates to purely local computation, which is how
single-device smoke tests run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax


@dataclass(frozen=True)
class ParallelContext:
    mesh: Optional[jax.sharding.Mesh] = None
    pod_axis: Optional[str] = None
    data_axis: Optional[str] = "data"
    model_axis: Optional[str] = "model"
    fsdp: bool = True
    # serve-time: shard the KV cache/sequence over the model axis (flash-decode)
    sequence_parallel_kv: bool = True
    # cast FSDP weight gathers to bf16 before the collective (§Perf)
    bf16_fsdp_gather: bool = False
    # SparseCore engine knobs (§Perf): bf16 embedding vectors on the wire,
    # all-to-all send capacity factor, and method override
    emb_wire_bf16: bool = False
    emb_capacity_factor: float = 2.0
    emb_method: str = "auto"
    # pipelined multi-group executor: fuse same-width groups into one
    # descriptor-stream launch and software-pipeline the per-group id/vector
    # exchanges (False = legacy one-launch-per-group dataflow)
    emb_pipeline: bool = True
    # serve fast path (§serve): decode attention backend — "auto" picks the
    # Pallas paged kernel on TPU and the dense XLA reference elsewhere;
    # "paged"/"dense" force one side.  decode_kv_block is the paged kernel's
    # KV block size (rows streamed per VMEM tile).
    decode_attn: str = "auto"
    decode_kv_block: int = 128

    def axis_size(self, name: Optional[str]) -> int:
        if name is None or self.mesh is None:
            return 1
        if name not in self.mesh.axis_names:
            return 1
        return self.mesh.shape[name]

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        """Mesh axes the global batch is sharded over."""
        return tuple(a for a in (self.pod_axis, self.data_axis)
                     if a is not None and self.axis_size(a) > 1)

    @property
    def fsdp_axes(self) -> Tuple[str, ...]:
        return self.batch_axes if self.fsdp else ()

    @property
    def model_axis_size(self) -> int:
        return self.axis_size(self.model_axis)

    @property
    def has_mesh(self) -> bool:
        return self.mesh is not None and any(
            s > 1 for s in self.mesh.shape.values())

    def spec(self, *axes) -> jax.sharding.PartitionSpec:
        """PartitionSpec helper that drops axes absent from the mesh."""
        def ok(a):
            if a is None:
                return None
            if isinstance(a, tuple):
                kept = tuple(x for x in a if self.axis_size(x) > 1)
                return kept if kept else None
            return a if self.axis_size(a) > 1 else None
        return jax.sharding.PartitionSpec(*(ok(a) for a in axes))


LOCAL = ParallelContext(mesh=None, pod_axis=None, data_axis=None,
                        model_axis=None, fsdp=False)


# ---------------------------------------------------------------------------
# Trace-time activation sharding hints
# ---------------------------------------------------------------------------
# Model code calls hint(x, "batch", None, "model") at layout-critical points;
# the names resolve against the active ParallelContext (set by the step
# builders around tracing).  Without an active context this is the identity,
# so single-device smoke tests are unaffected.

import contextlib
import contextvars

_ACTIVE: contextvars.ContextVar[Optional[ParallelContext]] = \
    contextvars.ContextVar("repro_parallel_ctx", default=None)


@contextlib.contextmanager
def activate(ctx: Optional[ParallelContext]):
    tok = _ACTIVE.set(ctx if (ctx is not None and ctx.has_mesh) else None)
    try:
        yield
    finally:
        _ACTIVE.reset(tok)


def active_ctx() -> Optional[ParallelContext]:
    return _ACTIVE.get()


def hint(x, *roles):
    """Apply a sharding constraint by role names.

    Roles: "batch" -> ctx.batch_axes, "model"/"heads" -> ctx.model_axis,
    "both" -> batch+model combined, None -> unsharded.  Any role whose axes
    don't divide the corresponding dim resolves to None.
    """
    ctx = _ACTIVE.get()
    if ctx is None or ctx.mesh is None:
        return x
    entries = []
    for dim, role in zip(x.shape, roles):
        if role is None:
            entries.append(None)
            continue
        if role == "batch":
            axes = tuple(ctx.batch_axes)
        elif role in ("model", "heads", "seq"):
            axes = (ctx.model_axis,) if ctx.model_axis_size > 1 else ()
        elif role == "both":
            axes = tuple(ctx.batch_axes)
            if ctx.model_axis_size > 1:
                axes = axes + (ctx.model_axis,)
        else:
            raise ValueError(role)
        size = 1
        for a in axes:
            size *= ctx.axis_size(a)
        if not axes or size <= 1 or dim % size != 0:
            entries.append(None)
        else:
            entries.append(axes if len(axes) > 1 else axes[0])
    spec = jax.sharding.PartitionSpec(*entries)
    return jax.lax.with_sharding_constraint(x, spec)
