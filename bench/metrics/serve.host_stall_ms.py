"""Device idle time per decode chunk while the serve engine holds the host:
the idle time of the traced window under the engine's spans, its waits
included, over the ``serve.decode`` spans that start in the window.  It
leaves out the driver's own loop between chunks.

The waits (``*.sync``) count because a chunk's idle gap runs from the end
of one program, across the host's work and the next call, to the start of
the next program on either side of a sync's edge, and the profiler aligns
the device's clock with the host's only to about a millisecond per session
(a decode program read 0.49 ms before its own call on a TPU v5e): where in
the gap the host's work ends and the wait begins is not measurable, while
the idle time under all the engine's spans is."""
from bench import spans as SP


def read(summary, job, out):
    sp = SP.for_job(job)
    chunks = sp.named("serve.decode") if sp else []
    if not chunks:
        return None
    idle = SP.stall_s(sp, "serve.", lambda name: False)
    return None if idle is None else 1e3 * idle / len(chunks)
