"""Device idle time per training step that the trainer's host work holds:
the idle time of the traced window under the trainer's spans, each instant
given to the innermost span open at it, leaving out ``train.log`` (where
the host reads a step's metrics back, the loop's one wait for the device),
over the ``train.step`` spans that start in the window."""
from bench import spans as SP


def read(summary, job, out):
    sp = SP.for_job(job)
    steps = sp.named("train.step") if sp else []
    if not steps:
        return None
    idle = SP.stall_s(sp, "train.", lambda name: name == "train.log")
    return None if idle is None else 1e3 * idle / len(steps)
