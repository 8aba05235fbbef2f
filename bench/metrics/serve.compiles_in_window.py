"""Programs that JAX lowered, and so traced and compiled or read from the
compile cache, while the engine served a chunk in the traced window: its
``lower_sharding_computation`` annotations that start inside a
``serve.step_chunk`` span.  Every shape is warmed up before the window, so
this should read 0."""
from bench import spans as SP


def read(summary, job, out):
    sp = SP.for_job(job)
    chunks = sp.named("serve.step_chunk") if sp else []
    if not chunks:
        return None
    return sum(1 for c in sp.lowerings
               if any(s.start <= c.start < s.end for s in chunks))
