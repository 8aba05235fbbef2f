"""Runs one cell of ``BENCHMARK.json`` and prints its result line.

Everything is found by name: the cell names a configuration (its file is
given in ``BENCHMARK.json``) and a traffic mix (``bench/traffic/<mix>.json``,
whose ``kind`` names the driver ``bench/drivers/<kind>.py``); the
configuration file names its program configuration in a ``program`` block
(``bench/program.py``) and its architecture, whose plain reference is
``bench/reference/<architecture>.py``; the cell's correctness limits are
``bench/limits/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  A configuration (of any architecture the
program's registry has), a cell, a mix or a metric is added as new files
and entries, with no edit to a file that is here.

A run: check that JAX holds the cell's chips and knows their peaks, turn on
the compile cache, let the driver set up, measure its window and check what
the window produced, then print the end-to-end metrics (``--trace 0``) or
the per-layer ones read from a profiler trace of part of the window
(``--trace 1``).  The last line on standard output is the result; the
numbers compared for ``correct``, each beside its limit, are the last lines
on standard error and the last key of the result.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

import jax

from bench import trace as TR
from bench.peaks import peaks_for
from bench.reference import INTERFACE

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX holds fewer chips than the cell asks for, or none at all."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with everything it names."""
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    reference: ModuleType           # bench/reference/<architecture>.py


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def find_cell(bench: dict, name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    config = load_json(root / cfg["file"])
    return Cell(name=name, chips=w["chips"], config=config,
                mix=load_json(root / "bench" / "traffic"
                              / f"{w['traffic']}.json"),
                limits=load_json(root / "bench" / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer,
                reference=reference(config["architecture"], root))


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    return _load(root / "bench" / "metrics" / f"{name}.py",
                 f"bench_metric_{name.replace('.', '_')}")


# one module per file, so that the functions a reference jits keep their
# compiled programs from one cell to the next in a process
@functools.lru_cache(maxsize=None)
def _load_reference(path: Path):
    return _load(path, f"bench_reference_{path.stem.replace('.', '_')}")


def reference(architecture: str, root: Path = ROOT) -> ModuleType:
    """The plain reference of ``architecture``: the module
    ``bench/reference/<architecture>.py`` under ``root``, which has to
    provide every function of ``bench.reference.INTERFACE``."""
    path = (root / "bench" / "reference" / f"{architecture}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(
            f"no reference for architecture {architecture!r}: "
            f"{path} is missing")
    mod = _load_reference(path)
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"the reference {path} lacks {missing}")
    return mod


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_chips(n: int) -> dict:
    """The device JAX reports; refuses anything but ``n`` or more TPUs
    whose peaks are known."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"needs {n} TPU(s), JAX found {len(devs)} "
                     f"{d.platform} device(s) ({d.device_kind})")
    if len(devs) < n:
        raise NoChip(f"needs {n} TPUs, JAX found {len(devs)}")
    peaks_for(d.device_kind)
    return describe_devices()


def describe_devices() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# what a driver is given and gives back
# ---------------------------------------------------------------------------

class Tracer:
    """Profiles the part of the window the driver chooses: ``start`` and
    ``stop`` are no-ops unless the run traces.  The traced window is the
    host annotation ``bench.traced_window``."""

    def __init__(self, on: bool):
        self.on = on
        self.dir: Optional[str] = None
        self.active = False
        self.done = False
        self._ann = None

    def start(self) -> None:
        if not self.on or self.active or self.done:
            return
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation(TR.WINDOW)
        self._ann.__enter__()
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active, self.done = False, True

    def summary(self) -> TR.Summary:
        return TR.reduce(TR.load(TR.find_xplane(self.dir)))

    def cleanup(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Job:
    cell: Cell
    seed: int
    seconds: float
    t_start: float                  # perf_counter at process start
    tracer: Tracer
    control: bool = False           # the control in the program's place
    peaks: Optional[dict] = None    # the chip's entry in bench/peaks.py


@dataclasses.dataclass
class Outcome:
    metrics: Dict[str, float]       # end-to-end, by name
    attempted: int
    failed: int
    checks: Dict[str, Dict[str, float]]   # name -> {"value", "limit"}
    memory_peak_bytes: int
    records: Dict[str, Any] = dataclasses.field(default_factory=dict)
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.checks.values())


def span(name: str):
    """A host annotation in the profiler's trace (cheap when not tracing)."""
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def result_line(job: Job, out: Outcome, device: dict,
                summary: Optional[TR.Summary]) -> dict:
    cell = job.cell
    metrics = {}
    if summary is None:
        for m in cell.end_to_end:
            if m["name"] in out.metrics:
                metrics[m["name"]] = {"value": out.metrics[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            value = metric_reader(m["name"]).read(summary, job, out)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=out.memory_peak_bytes)
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": dev}
    if summary is not None:
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        line["breakdown"] = summary.breakdown()
    line["checks"] = out.checks
    return line


def execute(job: Job, device: dict) -> dict:
    """Drive the cell and build its result line."""
    mod = driver(job.cell.mix["kind"])
    try:
        out = mod.run(job)
        summary = job.tracer.summary() if job.tracer.on else None
        line = result_line(job, out, device, summary)
    finally:
        job.tracer.stop()
        job.tracer.cleanup()
    print(json.dumps({"info": out.info}), flush=True)
    return line


def main(argv, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="judge the lower-precision control in the program's "
                    "place (the run is then not correct)")
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell = find_cell(bench, args.workload)
    # a configuration the program does not have fails here, before the chip
    from bench import program
    program.model_config(cell.config)
    try:
        device = require_chips(cell.chips)
    except Exception as e:                       # noqa: BLE001
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    job = Job(cell=cell, seed=args.seed, seconds=args.seconds,
              t_start=t_start, tracer=Tracer(bool(args.trace)),
              control=args.control, peaks=peaks_for(device["kind"]))
    line = execute(job, device)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
