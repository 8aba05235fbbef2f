"""Fault-tolerant, preemptible trainer (DESIGN.md §8).

Orchestrates: synthetic data -> sharded train step -> periodic checkpoints,
with the OCS scheduler in the loop: on an (injected or real) block failure
the scheduler swaps a spare block in (§2.3), and the trainer restores from
the last checkpoint and continues — the paper's checkpoint/restore,
everything-must-work HPC training style, made cheap by OCS re-routing.

Training is also an *elastic tenant*: `request_preempt` (driven by the
cluster layer's ``"preempt"`` `SliceEvent`) makes the loop checkpoint at
the next step boundary and return early, so a serving burst can reclaim
the blocks.  The checkpoint is slice-shape-elastic (`repro.train.
checkpoint`): a fresh `Trainer` on a *differently shaped* slice restores
it bitwise and continues the exact same loss curve — the data cursor is
just the step (the synthetic `Dataset` is pure in ``(seed, step)``).

On this CPU container the "mesh" is whatever devices exist; the fault and
preemption paths exercise the full restore logic regardless of scale.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.configs.base import (ModelConfig, OptimizerConfig, ParallelConfig,
                                RunConfig, ShapeConfig)
from repro.core.scheduler import SliceScheduler
from repro.data.synthetic import Dataset
from repro.launch import steps as STEPS
from repro.models import api
from repro.obs import Telemetry
from repro.optim import adam as OPT
from repro.parallel import sharding as SH
from repro.train import checkpoint as CKPT


@dataclasses.dataclass
class TrainerState:
    """Everything training needs to continue: parameters, optimizer state,
    and the global step (which doubles as the data cursor)."""
    params: Any
    opt_state: Any
    step: int


class Trainer:
    """Training loop bound to one mesh, with checkpoint/restore, fault
    drills, and cooperative preemption.

    Args:
      run: full `RunConfig` (model, shape, parallelism, optimizer).
      mesh: jax mesh to compile and run the train step on.
      ckpt_dir: checkpoint root (no checkpoints when None).
      ckpt_every: periodic checkpoint interval in steps.
      accum_steps: optional gradient-accumulation microsteps.
      slice_dims: chip geometry of the slice this trainer runs on, recorded
        in checkpoint manifests so an elastic resume can report the shape
        change (purely observational).
    """

    def __init__(self, run: RunConfig, mesh, *, ckpt_dir: Optional[str] = None,
                 ckpt_every: int = 50, accum_steps: Optional[int] = None,
                 slice_dims: Optional[tuple] = None,
                 obs: Optional[Telemetry] = None,
                 obs_labels: Optional[Dict[str, Any]] = None):
        self.run = run
        self.mesh = mesh
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.slice_dims = slice_dims
        self.preempt_requested = False
        self.preempted = False
        self.ctx = SH.make_context(mesh, run.parallel)
        self.dataset = Dataset(run.model, run.shape, seed=run.seed)
        # the per-step metric log lives in the registry as a Series;
        # `metrics_log` below is a view of its samples, so the attribute
        # surface (and everything reading it) is unchanged
        self.obs = obs if obs is not None else Telemetry()
        self._obs_labels = dict(obs_labels or {})
        self._series = self.obs.metrics.series("train.metrics",
                                               **self._obs_labels)

        with jax.set_mesh(mesh):
            # ONE step builder for every entry point (shapes_and_shardings
            # -> make_train_step), so ParallelConfig knobs — notably
            # grad_compression — can't silently apply on one path only
            args, in_sh, out_sh, step = STEPS.shapes_and_shardings(
                run.model, run.shape, run.parallel, run.optimizer, self.ctx,
                accum_steps=accum_steps)
            self._in_sh = jax.tree.map(self._named, in_sh,
                                       is_leaf=self._is_spec)
            self._out_sh = jax.tree.map(self._named, out_sh,
                                        is_leaf=self._is_spec)
            self.train_step = jax.jit(step, in_shardings=self._in_sh,
                                      out_shardings=self._out_sh,
                                      donate_argnums=(0, 1))

    @property
    def metrics_log(self) -> List[Dict[str, float]]:
        """Per-step metric dicts (a view of the registry Series' samples —
        the list object is live, appends land in the registry)."""
        return self._series.samples

    def _named(self, s):
        if s is None:
            return None
        return jax.sharding.NamedSharding(self.mesh, s)

    @staticmethod
    def _is_spec(x):
        return isinstance(x, jax.sharding.PartitionSpec) or x is None

    # -- state ------------------------------------------------------------------

    def init_state(self) -> TrainerState:
        """Fresh params + optimizer state at step 0 (seeded by the run)."""
        key = jax.random.PRNGKey(self.run.seed)
        with jax.set_mesh(self.mesh):
            params = jax.jit(
                lambda: api.init_params(self.run.model, key, self.ctx),
                out_shardings=self._in_sh[0])()
            opt = jax.jit(
                lambda p: OPT.init(self.run.optimizer, p),
                out_shardings=self._in_sh[1])(params)
        return TrainerState(params, opt, 0)

    def save(self, state: TrainerState) -> None:
        """Checkpoint ``state`` (params + optimizer + data cursor).  The
        manifest records the data seed and source-slice geometry, so a
        resume on a different slice can verify it continues the same data
        stream."""
        if not self.ckpt_dir:
            return
        with self.obs.span("train.save", cat="train", track="train",
                           step=state.step):
            CKPT.save(self.ckpt_dir, state.step,
                      {"params": state.params, "opt": state.opt_state},
                      extra={"step": state.step, "data_seed": self.run.seed,
                             "slice_dims": (list(self.slice_dims)
                                            if self.slice_dims else None)})

    def request_preempt(self) -> None:
        """Cooperative preemption: ask the running loop to checkpoint and
        stop at the next step boundary (idempotent; safe before `train`
        too — the loop then checkpoints immediately and returns).

        Persistence needs ``ckpt_dir``: without one the loop still stops
        and returns its state, but nothing lands on disk — the caller must
        keep the returned `TrainerState` (passing it back to `train`)
        or the resume falls back to a fresh init."""
        self.preempt_requested = True

    def restore(self, *, mesh=None) -> Optional[TrainerState]:
        """Restore latest checkpoint, optionally onto a different mesh
        (elastic rescale path).  Returns None with no checkpoint on disk."""
        if not self.ckpt_dir or CKPT.latest_step(self.ckpt_dir) is None:
            return None
        key = jax.random.PRNGKey(self.run.seed)
        params_shape = jax.eval_shape(
            lambda: api.init_params(self.run.model, key, self.ctx))
        opt_shape = jax.eval_shape(
            lambda: OPT.init(self.run.optimizer, jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                params_shape)))
        tree, step, extra = CKPT.restore(
            self.ckpt_dir, {"params": params_shape, "opt": opt_shape},
            shardings={"params": self._in_sh[0], "opt": self._in_sh[1]})
        saved_seed = extra.get("data_seed")
        assert saved_seed is None or saved_seed == self.run.seed, (
            f"checkpoint was trained on data seed {saved_seed}, this run "
            f"uses {self.run.seed}: resuming would fork the data stream")
        return TrainerState(tree["params"], tree["opt"], step)

    # -- loop ------------------------------------------------------------------

    def _put_batch(self, step: int):
        batch = self.dataset.batch(step)
        return jax.device_put(batch, self._in_sh[2])

    def _log(self, metrics, step: int, wall_s: float) -> None:
        """Read a step's metrics back (the loop's one wait for the device)
        into the series."""
        with self.obs.span("train.log", cat="train", track="train"):
            m = {k: float(v) for k, v in metrics.items()}
        m.update(step=step, wall_s=round(wall_s, 2))
        self._series.append(m)
        # wire accounting rides the registry too: last-observed
        # per-step payload bytes from the compressed collectives
        for k in ("wire_bytes", "wire_bytes_full", "wire_overhead_bytes"):
            if k in m:
                self.obs.metrics.gauge(
                    f"train.{k}", **self._obs_labels).set(m[k])

    def train(self, num_steps: int, *, state: Optional[TrainerState] = None,
              fail_at: Optional[int] = None,
              preempt_at: Optional[int] = None,
              scheduler: Optional[SliceScheduler] = None,
              job_id: Optional[int] = None,
              log_every: int = 10,
              on_step: Optional[Callable[[int, float], None]] = None
              ) -> TrainerState:
        """Run the loop to ``num_steps`` (absolute step count).

        Args:
          state: state to continue from (default: latest checkpoint, else a
            fresh init).
          fail_at: inject a block failure at this step — the §2.3 drill:
            the scheduler swaps in a spare and training restores from the
            last checkpoint.
          preempt_at: inject `request_preempt` at this step (tests the
            cooperative-eviction path without a cluster driver).
          scheduler/job_id: OCS scheduler wiring for the fault drill.
          log_every: metric logging period.
          on_step: called after every executed step, once its results are
            ready, with ``(step, step_wall_s)`` — the hook the straggler
            detector rides (`TrainSession.run` feeds per-block step times
            from it).

        Returns the final `TrainerState`.  If a preemption request arrived
        (externally or via ``preempt_at``), the loop checkpointed, set
        `preempted`, and returned early — the caller frees the slice and
        resumes later from the checkpoint, on any slice shape."""
        state = state or self.restore() or self.init_state()
        t0 = time.perf_counter()
        step = state.step
        self.preempted = False
        while step < num_steps:
            if preempt_at is not None and step == preempt_at:
                preempt_at = None
                self.request_preempt()
            if self.preempt_requested:
                # cooperative eviction: persist everything (params, opt
                # state, data cursor = step) and hand the slice back
                self.save(state)
                self.preempt_requested = False
                self.preempted = True
                self._series.append({"step": step, "preempt": 1.0})
                self.obs.event("train.preempt", cat="train", track="train",
                               step=step, **self._obs_labels)
                return state
            if fail_at is not None and step == fail_at:
                # -- simulated block failure (TrainSession.run drives this)
                if scheduler is not None and job_id is not None:
                    blk = scheduler.jobs[job_id].blocks[0]
                    scheduler.fail_block(blk)
                fail_at = None
                restored = self.restore()
                if restored is not None:
                    state = restored
                    step = state.step
                    self._series.append({"step": step, "event": 1.0})
                    self.obs.event("train.restore", cat="train",
                                   track="train", step=step,
                                   **self._obs_labels)
                    continue
            t_step = time.perf_counter()
            with self.obs.span("train.step", cat="train", track="train",
                               step=step):
                with self.obs.span("train.batch", cat="train",
                                   track="train"):
                    batch = self._put_batch(step)
                with self.obs.span("train.dispatch", cat="train",
                                   track="train"), jax.set_mesh(self.mesh):
                    params, opt, metrics = self.train_step(
                        state.params, state.opt_state, batch)
                state = TrainerState(params, opt, step + 1)
                step += 1
                if on_step is not None:
                    # dispatch is asynchronous: wait for the step to finish
                    # so the hook sees its run time, not the dispatch
                    # latency
                    jax.block_until_ready((params, metrics))
                    on_step(step, time.perf_counter() - t_step)
                if step % log_every == 0 or step == num_steps:
                    self._log(metrics, step, time.perf_counter() - t0)
            if self.ckpt_dir and step % self.ckpt_every == 0:
                self.save(state)
        if self.preempt_requested:
            # a request that arrived with no steps left to run (entered at
            # step >= num_steps, or raced the final step): service it here
            # so the flag never leaks into the next call and the caller
            # still gets the checkpointed/preempted contract
            self.save(state)
            self.preempt_requested = False
            self.preempted = True
            self._series.append({"step": step, "preempt": 1.0})
            self.obs.event("train.preempt", cat="train", track="train",
                           step=step, **self._obs_labels)
            return state
        if self.ckpt_dir:
            self.save(state)
        return state
