"""The program's own spans in a profiler trace, beside the device's idle time.

``repro.obs`` writes every span of the serve engine and the trainer into the
profiler's trace as a host annotation (``serve.*``, ``train.*``) whose args
are typed stats, on the clock the device planes use.  ``load`` reads them,
and JAX's own lowering annotation, from a run's ``.xplane.pb``: those that
overlap ``bench.traced_window``.  The device's idle intervals in the window
come from ``bench/trace.py``'s ``load``, ``clip``, ``union`` and ``gaps``.
Each path is parsed once: the per-layer readers share ``for_job``'s cache.

``idle_by_span`` gives each instant of device idle time to the innermost
program span open at it, cutting the idle intervals exactly at every span's
edges: the host stall of a span is what the device waited for it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

from bench import trace as TR

PREFIXES = ("serve.", "train.")
# JAX's annotation for lowering a jitted program to HLO: once per program it
# has not run before, followed by ``backend_compile_and_load`` where the
# persistent compile cache misses
LOWERING = "lower_sharding_computation"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float            # seconds, on the device trace's clock
    end: float
    args: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Spans:
    path: str
    window: Tuple[float, float]
    spans: List[Span]           # the program's that overlap the window
    lowerings: List[Span]       # JAX's lowering of a program, likewise

    def named(self, name: str) -> List[Span]:
        """Spans called ``name`` that start inside the window."""
        lo = self.window[0]
        return [s for s in self.spans if s.name == name and s.start >= lo]

    @functools.cached_property
    def idle(self) -> Dict[int, List[Tuple[float, float]]]:
        """Per chip, the intervals of the window in which no operation
        ran on the device."""
        tr = TR.load(self.path)
        lo, hi = self.window
        return {chip: TR.gaps(TR.union(TR.clip(ops, lo, hi)), lo, hi)
                for chip, ops in tr.ops.items()}


def load(path: str) -> Spans:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: List[Span] = []
    window = None
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if not (name.startswith(PREFIXES) or name == LOWERING
                        or name == TR.WINDOW):
                    continue
                t0 = e.start_ns * 1e-9
                t1 = t0 + e.duration_ns * 1e-9
                if name == TR.WINDOW:
                    window = (t0, t1)
                else:
                    host.append(Span(name, t0, t1, dict(e.stats)))
    if window is None:
        raise ValueError(f"trace holds no {TR.WINDOW} annotation")
    lo, hi = window
    inside = sorted((s for s in host if s.end > lo and s.start < hi),
                    key=lambda s: s.start)
    return Spans(path, window,
                 [s for s in inside if s.name.startswith(PREFIXES)],
                 [s for s in inside if s.name == LOWERING])


@functools.lru_cache(maxsize=2)
def _cached(path: str) -> Spans:
    return load(path)


def for_job(job) -> Optional[Spans]:
    """The spans of the run's trace, or None where the run traced
    nothing."""
    if job.tracer.dir is None:
        return None
    return _cached(TR.find_xplane(job.tracer.dir))


def idle_by_span(idle: List[Tuple[float, float]], spans: List[Span]
                 ) -> Dict[str, float]:
    """Seconds of ``idle`` under each span name, each instant given to the
    innermost (shortest) span open at it; idle time under no span is left
    out."""
    out: Dict[str, float] = {}
    for a, b in idle:
        over = [s for s in spans if s.start < b and s.end > a]
        if not over:
            continue
        cuts = sorted({a, b} | {t for s in over for t in (s.start, s.end)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            open_ = [s for s in over if s.start <= mid < s.end]
            if open_:
                s = min(open_, key=lambda s: s.end - s.start)
                out[s.name] = out.get(s.name, 0.0) + (y - x)
    return out


def stall_s(sp: Spans, prefix: str, waits) -> Optional[float]:
    """Device idle time, mean over the chips, under the ``prefix`` spans
    other than those for which ``waits(name)`` holds (the spans in which
    the host waits for the device)."""
    if not sp.idle:
        return None
    spans = [s for s in sp.spans if s.name.startswith(prefix)]
    total = sum(v for chip_idle in sp.idle.values()
                for k, v in idle_by_span(chip_idle, spans).items()
                if not waits(k))
    return total / len(sp.idle)
