"""Training steps through ``Supercomputer().allocate`` -> ``Slice.train``
-> ``TrainSession.run``, whose trainer makes each batch with the program's
own ``Dataset`` and dispatches the step asynchronously.

Set-up builds one trainer, gives it weights made on the device from the
seed, and drives it through the first ``check_steps`` steps by the same
call the window makes (compiling the step on the first).  It keeps what the
correctness check needs: the loss of each step, the norm of each leaf of the
first gradient as the optimizer got it (its first moment after one step,
over ``1 - b1``) and of each leaf's change over the steps, taken on the
device against the weights made anew from the seed.  The window then runs
the same trainer on in blocks of ``block_steps`` until ``--seconds`` have
passed, and blocks once at the end: ``train_step_ms`` is the whole window
over the steps it ran.

Afterwards the trainer is dropped and the float32 reference runs the same
first steps on the same tokens from the same weights; the norm of each leaf
of the first gradient, and of each leaf's change over the steps (the worst
leaf and the median leaf) must lie within the limits of the reference's.
The losses of both are recorded beside them.  With
``--control`` the float8 reference's steps are judged in the program's
place, so the run is not correct.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator as GEN
from bench import harness as H
from bench import program as PROG


@jax.jit
def _leaf_norms(new, old):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - old[k].astype(jnp.float32))))
        for k, x in new.items()}


def _norms(flatten, tree, old=None) -> dict:
    """Per-leaf norms of ``tree``, or of its change from ``old``, flattened
    by the reference's ``flatten``."""
    new = flatten(tree)
    old = flatten(old) if old is not None else {
        k: jnp.zeros((), x.dtype) for k, x in new.items()}
    return {k: float(v) for k, v in _leaf_norms(new, old).items()}


def leaf_gaps(got: dict, want: dict, leaves) -> dict:
    """Per leaf, the gap between the program's and the reference's norm,
    over the larger of that leaf's reference norm and the median leaf's."""
    med = float(np.median([want[k] for k in leaves]))
    return {k: abs(got[k] - want[k]) / max(want[k], med) for k in leaves}


def worst_leaf_gap(got: dict, want: dict, leaves) -> float:
    return max(leaf_gaps(got, want, leaves).values())


def _moved(r_g1: dict) -> list:
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone, as a key's bias does under softmax
    med = float(np.median(list(r_g1.values())))
    return [k for k in r_g1 if r_g1[k] >= 1e-3 * med]


def run(job: H.Job) -> H.Outcome:
    c, mix, REF = job.cell.config, job.cell.mix, job.cell.reference
    opt = mix["optimizer"]
    run_cfg = PROG.RunConfig(
        model=PROG.model_config(c),
        shape=PROG.ShapeConfig("bench", "train", mix["seq_len"],
                               mix["batch"]),
        parallel=PROG.ParallelConfig(remat=mix["remat"]),
        optimizer=PROG.OptimizerConfig(**opt), seed=job.seed)
    n_check, block = mix["check_steps"], mix["block_steps"]
    trace_from = min(mix["trace_from_s"], job.seconds / 4)

    weights = REF.init_weights(c, job.seed)
    phases = {"start_s": time.perf_counter() - job.t_start}
    with PROG.Supercomputer().allocate(PROG.SLICE) as sl:
        session = sl.train(run_cfg)
        state = PROG.TrainerState(
            weights, jax.jit(lambda p: PROG.optimizer_init(
                run_cfg.optimizer, p))(weights), 0)
        del weights
        jax.block_until_ready(state)
        phases["weights_s"] = time.perf_counter() - job.t_start
        state = session.run(1, state=state, log_every=1)
        g1 = {k: v / (1.0 - opt["b1"])
              for k, v in _norms(REF.flatten, state.opt_state.mu).items()}
        phases["first_step_s"] = time.perf_counter() - job.t_start
        state = session.run(n_check, state=state, log_every=1)
        losses = [m["loss"] for m in session.metrics_log if "loss" in m]
        change = _norms(REF.flatten, state.params,
                        REF.init_weights(c, job.seed))

        t0 = time.perf_counter()
        setup_s = t0 - job.t_start
        phases["checked_steps_s"] = setup_s
        steps, now, traced_steps = 0, 0.0, 0
        while now < job.seconds:
            if job.tracer.on and not job.tracer.done and now >= trace_from:
                if job.tracer.active:
                    job.tracer.stop()
                else:
                    job.tracer.start()
            with H.span("train_block"):
                state = session.run(state.step + block, state=state,
                                    log_every=10**9)
            if job.tracer.active:
                traced_steps += block
            steps += block
            now = time.perf_counter() - t0
        with H.span("block_until_ready"):
            jax.block_until_ready(state.params)
        window_s = time.perf_counter() - t0
        job.tracer.stop()
        mem = H.memory_peak_bytes()
        session.state = None            # the slice keeps its sessions
        session.close()
        del state, session

    batches = [GEN.lm_batch(job.seed, s, mix["batch"], mix["seq_len"],
                            c["vocab_size"]) for s in range(n_check)]
    w0 = REF.init_weights(c, job.seed)
    ref = REF.train_steps(c, opt, w0, batches)
    checks = _checks(job.cell.limits, g1, change, ref)
    info = {"cell": job.cell.name, "seed": job.seed, "window_s": window_s,
            "steps": steps, "losses": losses, "reference_losses": ref[0],
            "loss_gaps": _loss_gaps(losses, ref[0]),
            "setup_phases": phases,
            "update_leaf_gaps": leaf_gaps(change, ref[2], _moved(ref[1]))}
    if job.control:
        # the control, the reference one precision step down, stands in the
        # program's place; and a fault planted in the reference put there:
        # the mean taken over half of each batch
        info["program"] = {k: v["value"] for k, v in checks.items()}
        half = [(t[:len(t) // 2], l[:len(l) // 2]) for t, l in batches]
        bad = REF.train_steps(c, opt, w0, half)
        info["fault_half_batch"] = {
            "loss_gaps": _loss_gaps(bad[0], ref[0]),
            **{k: v["value"] for k, v in _checks(
                job.cell.limits, *bad[1:], ref).items()}}
        ctl = REF.train_steps(c, opt, w0, batches, quant="fp8")
        info["control_loss_gaps"] = _loss_gaps(ctl[0], ref[0])
        info["control_update_leaf_gaps"] = leaf_gaps(ctl[2], ref[2],
                                                     _moved(ref[1]))
        checks = _checks(job.cell.limits, *ctl[1:], ref)
    return H.Outcome(
        metrics={"setup_s": setup_s,
                 "train_step_ms": window_s * 1e3 / steps},
        attempted=steps, failed=0, checks=checks, memory_peak_bytes=mem,
        records={"traced_steps": traced_steps}, info=info)


def _loss_gaps(losses, r_losses) -> list:
    return [abs(a - b) / abs(b) for a, b in zip(losses, r_losses)]


def _checks(lim, g1, change, ref) -> dict:
    # The losses are recorded, not compared: no limit parts the program
    # from a fault there.  The first step's gap reads alike for the program,
    # the control and half of the batch; the second stands on the spike
    # that the first full-rate update makes from random weights, where the
    # same rounding reads ten to a hundred times larger from seed to seed.
    _, r_g1, r_change = ref
    moved = _moved(r_g1)
    return {"first_grad_gap": {"value": worst_leaf_gap(g1, r_g1, r_g1),
                               "limit": lim["first_grad_gap"]},
            "update_gap": {"value": worst_leaf_gap(change, r_change, moved),
                           "limit": lim["update_gap"]},
            # the worst leaf's change swings from seed to seed with the
            # rounding that the first full-rate step amplifies; the median
            # leaf's is steady and parts the program from the control
            "update_gap_median": {
                "value": float(np.median(list(
                    leaf_gaps(change, r_change, moved).values()))),
                "limit": lim["update_gap_median"]}}
