"""Open-loop serving of a model whose layers route each token to a few of
their experts: ``serve_open_loop``'s window, with a check that also holds
the share of served tokens whose logit lies far below the reference's best.

A top-k router is discontinuous.  Where two experts' biased scores lie
within rounding of each other, a program computing in bf16 and the float32
reference pick different experts, and that position's logits move by as
much as the float8 control moves its worst ones (LFM2-8B-A1B at width
2048: up to ~1.5 for the program, 1.2-1.4 for the control; PERF.md
section 6).  The widest gap alone cannot tell them apart.  How often a
gap is wide can: the program's exceeds 0.1 on ~3% of checked tokens, the
control's on ~36%.  So a cell of this kind is correct when both hold:

``share_above_margin``
    the share of checked tokens whose gap exceeds the limits file's
    ``gap_margin``: the precision the program keeps;
``max_logit_gap``
    the widest gap, above what routing flips reach and below what a wrong
    token reads: a token that is plain wrong.

The sample of requests is drawn as ``serve_open_loop`` draws it.  With
``--control`` the float8 reference's own choice at each position stands in
the program's place for both.
"""
from __future__ import annotations

import importlib.util

import numpy as np


def _check(job, c, reqs, served, done, width, n_out, weights):
    lim, REF = job.cell.limits, job.cell.reference
    if not done:
        return {"completed_requests": {"value": 1.0, "limit": 0.0}}, [], {}
    rng = np.random.default_rng([job.seed % 2**63, 4])
    longest = max(done, key=lambda k: len(served[k]))
    rest = [k for k in done if k != longest]
    n = min(len(rest), lim["sample_requests"] - 1)
    sample = [longest] + [rest[j] for j in
                          sorted(rng.choice(len(rest), n, replace=False))]
    pairs = [REF.served_gaps(c, weights, reqs.prompts[k], served[k], width,
                             n_out, control=job.control) for k in sample]
    gaps = np.concatenate([g for g, _ in pairs])
    readings = {}
    if job.control:
        readings["program_max_logit_gap"] = float(gaps.max())
        readings["program_share_above_margin"] = float(
            np.mean(gaps > lim["gap_margin"]))
        # a fault planted where tokens are produced: one token of the
        # longest request replaced by another drawn from the seed
        k = sample[0]
        bad = served[k].copy()
        j = len(bad) // 2
        bad[j] = (bad[j] + 1 + rng.integers(c["vocab_size"] - 1)) \
            % c["vocab_size"]
        fault, _ = REF.served_gaps(c, weights, reqs.prompts[k], bad, width,
                                   n_out)
        readings["fault_altered_token_max_logit_gap"] = float(fault.max())
        gaps = np.concatenate([cg for _, cg in pairs])
    checks = {"max_logit_gap": {"value": float(gaps.max()),
                                "limit": lim["max_logit_gap"]},
              "share_above_margin": {
                  "value": float(np.mean(gaps > lim["gap_margin"])),
                  "limit": lim["share_above_margin"]}}
    return checks, sample, readings


def _open_loop_with(check):
    """``serve_open_loop`` loaded once more as a module of its own, with
    ``check`` in its ``_check``'s place; the module the other serving
    cells run is left as it is."""
    spec = importlib.util.find_spec("bench.drivers.serve_open_loop")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._check = check
    return mod


run = _open_loop_with(_check).run
