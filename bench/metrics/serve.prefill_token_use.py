"""Share of the admission program's token rows that held prompt tokens: over
the ``serve.admit.prefill`` spans that start in the traced window (one per
dispatch), the prompt tokens each dispatch held (``tokens``) over its
fixed width (``width``: slots x suffix length).  Rows of slots not being
admitted, and the tail of a prompt's last dispatch, are computed all the
same."""
from bench import spans as SP


def read(summary, job, out):
    sp = SP.for_job(job)
    runs = sp.named("serve.admit.prefill") if sp else []
    width = sum(s.args["width"] for s in runs)
    if not width:
        return None
    return 100.0 * sum(s.args["tokens"] for s in runs) / width
