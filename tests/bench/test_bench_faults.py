"""Each cell's check for ``correct`` must fail where the timed path is
broken: the control (the reference one precision step down) and the faults
each cell can have, planted underneath a run that skips only the harness's
look for a chip.  The sizes are cut to what a CPU test run holds."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import steps as STEPS
from repro.models import api
from repro.optim import adam as OPT
from repro.serve import engine as ENGINE

import bench_tiny as tiny

SEEDS = (2**33 + 5, 2**40 + 17, 123456789)


@pytest.fixture
def fresh_programs():
    """Serve programs compiled after a patch, and dropped after it."""
    ENGINE._pooled_programs.cache_clear()
    yield
    ENGINE._pooled_programs.cache_clear()


def _serve_cell():
    return tiny.serve_cell(output_len={"dist": "lognormal", "median": 16,
                                       "sigma": 0.5, "min": 8, "max": 32})


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_reads_above_the_program(seed):
    """The control in the program's place makes the run not correct; the
    program itself, on the same requests, is within the limit."""
    out = tiny.run(_serve_cell(), seed=seed, control=True)
    assert not out.correct and out.failed == 0, out.checks
    limit = out.checks["max_logit_gap"]["limit"]
    prog = out.info["program_max_logit_gap"]
    assert prog <= limit
    assert out.checks["max_logit_gap"]["value"] > max(3 * prog, limit)
    assert out.info["fault_altered_token_max_logit_gap"] > limit


def test_serve_altered_token_is_not_correct(monkeypatch, fresh_programs):
    real = api.decode_n

    def altered(cfg, *a, **kw):
        toks, cache, lens, last = real(cfg, *a, **kw)
        return (toks + 1) % cfg.vocab_size, cache, lens, last

    monkeypatch.setattr(api, "decode_n", altered)
    out = tiny.run(_serve_cell())
    assert out.attempted > 0
    assert not out.correct, out.checks


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def test_train_control_and_half_batch_read_above_the_program():
    """The control in the program's place makes the run not correct; the
    program itself, and the half-batch fault, read as the limits expect."""
    out = tiny.run(tiny.train_cell(), control=True)
    assert not out.correct, out.checks
    prog = out.info["program"]
    assert all(prog[k] <= c["limit"] for k, c in out.checks.items()), prog
    for got in ({k: c["value"] for k, c in out.checks.items()},
                out.info["fault_half_batch"]):
        assert any(got[k] > 3 * prog[k] and got[k] > out.checks[k]["limit"]
                   for k in prog), (got, prog)


def test_train_state_left_unchanged_is_not_correct(monkeypatch):
    def unchanged(cfg, params, grads, state):
        return params, state, {"grad_norm": jnp.float32(0),
                               "lr": jnp.float32(0)}

    monkeypatch.setattr(OPT, "apply", unchanged)
    out = tiny.run(tiny.train_cell())
    assert out.checks["update_gap"]["value"] == pytest.approx(1.0)
    assert not out.correct


def test_train_half_batch_is_not_correct(monkeypatch):
    real = STEPS.loss_fn

    def half(cfg, params, batch, ctx, **kw):
        n = batch["tokens"].shape[0]
        return real(cfg, params, {k: v[: max(1, n // 2)]
                                  for k, v in batch.items()}, ctx, **kw)

    monkeypatch.setattr(STEPS, "loss_fn", half)
    out = tiny.run(tiny.train_cell(), seconds=1.0)
    assert not out.correct, out.checks
    assert np.isfinite(out.metrics["train_step_ms"])
