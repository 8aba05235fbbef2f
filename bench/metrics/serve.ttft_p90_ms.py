"""Time to first token, p90, from scheduled arrival, on the host's clock.
In a traced run it covers the requests served before the profiler's stop,
which holds the serving loop for seconds, and leaves out those waiting
while it started.  The tail swings with where arrivals fall against the
chunk boundaries, by more than an end-to-end bound may allow, so it is
read here, beside the steadier ``tpot_p90_ms``."""


def read(summary, job, out):
    return out.metrics.get("ttft_p90_ms")
