"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``: the device planes (``/device:TPU:n``) with
their "XLA Ops" line (one event per operation run) and "XLA Modules" line
(one event per jitted program run), and the benchmark's own host
annotations (names starting ``bench.``).  ``reduce`` clips all of it to the
traced window and computes, per chip and averaged over the chips, the busy
time (the union of the operations' intervals), the time of each program and
of each operation by name, and the idle gaps, each labelled with the host
annotation that was open at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.traced_window"
# idle gaps shorter than this lie between the operations of one program;
# longer ones are labelled with what the host was doing
SHORT_GAP_S = 10e-6
SHORT_GAP = "between operations (< 10 us)"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # seconds
    end: float


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]          # chip -> operations
    modules: Dict[int, List[Event]]      # chip -> program runs
    host: List[Event]                    # the benchmark's annotations


def find_xplane(root: str) -> str:
    found = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                evs = [Event(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
                (ops if line.name == OPS_LINE else modules)[
                    int(m.group(1))] = evs
            elif not m and plane.name.startswith("/host"):
                host += [Event(e.name, e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events
                         if e.name.startswith(HOST_PREFIX)]
    return Trace(ops, modules, sorted(host, key=lambda e: e.start))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def union(events: List[Event]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if merged and e.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end)
        else:
            merged.append([e.start, e.end])
    return [(a, b) for a, b in merged]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(host: List[Event], t: float) -> str:
    """The innermost benchmark annotation open at ``t``."""
    best: Optional[Event] = None
    for e in host:
        if e.start <= t <= e.end and e.name != WINDOW and (
                best is None or e.end - e.start < best.end - best.start):
            best = e
    return best.name if best else "outside annotations"


def self_times(events: List[Event]) -> List[float]:
    """Each event's duration less that of the events nested in it (a
    device's op line nests the operations of a loop inside the loop)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    own = [e.end - e.start for e in events]
    stack: List[int] = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= events[stack[-1]].end:
            own[stack[-1]] -= e.end - e.start
        stack.append(i)
    return own


_OP = re.compile(r"^(%\S+) = (.*?) ([\w-]+)\(")


def short_name(name: str) -> str:
    """``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` to
    ``%fusion.3 fusion bf16[8,128]``: instruction, opcode, result type."""
    m = _OP.match(name)
    if not m:
        return name[:120]
    kind = re.sub(r"\{[^{}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)} {kind[:80]}"


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                        # mean over chips
    chips: int
    programs: Dict[str, List[float]]     # program -> seconds of each run
                                         # wholly inside the window
    ops_s: Dict[str, float]              # op -> self seconds, mean per chip
    op_events: Dict[str, List[float]]    # op name -> durations, all chips
    idle_s: Dict[str, float]             # host label -> idle s, mean per chip
    idle_gaps: Dict[str, int]            # host label -> gaps, all chips
    longest_gap: Dict[str, float]        # host label -> longest gap

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_s(self, pattern: str) -> List[float]:
        """Run times of the programs whose name holds ``pattern``."""
        return [d for name, ds in self.programs.items() if pattern in name
                for d in ds]

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[f"{k} ({self.idle_gaps[k]} gaps, longest "
                               f"{self.longest_gap[k]} s)", v]
                              for k, v in idle]}


def window_of(tr: Trace) -> Tuple[float, float]:
    """The traced window: the ``bench.traced_window`` annotation."""
    for e in tr.host:
        if e.name == WINDOW:
            return e.start, e.end
    raise ValueError(f"trace holds no {WINDOW} annotation")


def reduce(tr: Trace, lo: Optional[float] = None,
           hi: Optional[float] = None) -> Summary:
    if lo is None or hi is None:
        lo, hi = window_of(tr)
    chips = sorted(tr.ops)
    if not chips:
        raise ValueError("trace holds no device operations")
    busy = 0.0
    programs: Dict[str, List[float]] = defaultdict(list)
    ops_s: Dict[str, float] = defaultdict(float)
    op_events: Dict[str, List[float]] = defaultdict(list)
    idle_s: Dict[str, float] = defaultdict(float)
    idle_n: Dict[str, int] = defaultdict(int)
    longest: Dict[str, float] = defaultdict(float)
    for chip in chips:
        ops = clip(tr.ops[chip], lo, hi)
        merged = union(ops)
        busy += sum(b - a for a, b in merged)
        for e, own in zip(ops, self_times(ops)):
            ops_s[short_name(e.name)] += own / len(chips)
            op_events[e.name].append(e.end - e.start)
        for e in tr.modules.get(chip, []):
            if lo <= e.start and e.end <= hi:
                programs[_program(e.name)].append(e.end - e.start)
        for a, b in gaps(merged, lo, hi):
            k = (SHORT_GAP if b - a < SHORT_GAP_S
                 else label_at(tr.host, (a + b) / 2))
            idle_s[k] += (b - a) / len(chips)
            idle_n[k] += 1
            longest[k] = max(longest[k], b - a)
    return Summary(hi - lo, busy / len(chips), len(chips), dict(programs),
                   dict(ops_s), dict(op_events), dict(idle_s), dict(idle_n),
                   dict(longest))


def _program(name: str) -> str:
    """A program's name without the run id the profiler appends."""
    return re.sub(r"\(\d+\)$", "", name)
