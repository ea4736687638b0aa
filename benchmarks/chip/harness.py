"""The chip benchmark's harness: one run of one cell.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file that the harness finds by name:

  configs/<config>.json     a deployment: table size, fields, metric, the
                            FastVAT settings, what is assumed
  traffic/<mix>.json        a traffic mix; its "loop" names the loop kind
                            ("library": one caller back to back) and the
                            rest are its parameters
  workloads/<cell>.json     a cell: configuration, mix, chips, the length of
                            a traced window, and the limit of each number
                            that decides ``correct``
  metrics/<metric>.py       one reader per metric named in BENCHMARK.json:
                            ``read(run)`` returns a number or None
  counts/<kernel>.py        operations and bytes a kernel's problem needs
  peaks.json                the chip's peaks, keyed by ``device_kind``

The loop kind is the code here; a new cell of that kind is data.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a bad cell file)."""


# ------------------------------------------------------------ the data ----

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def list_workloads(bench_dir: Path = HERE) -> list[str]:
    """Names of every cell file under ``workloads/``."""
    return sorted(p.stem for p in (bench_dir / "workloads").glob("*.json"))


@dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    trace_seconds: float
    limits: dict

    @property
    def loop(self) -> str:
        return self.mix["loop"]


def load_cell(name: str, bench_dir: Path = HERE) -> Cell:
    path = bench_dir / "workloads" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no workload {name!r}; known: "
                         f"{list_workloads(bench_dir)}")
    w = load_json(path)
    mix = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    if mix.get("loop") not in LOOPS:
        raise BenchError(f"traffic {w['traffic']!r} names loop "
                         f"{mix.get('loop')!r}; known: {sorted(LOOPS)}")
    return Cell(name=name,
                config=load_json(bench_dir / "configs" / f"{w['config']}.json"),
                mix=mix, chips=int(w["chips"]),
                trace_seconds=float(w["trace_seconds"]),
                limits=dict(w["limits"]))


def metric_entries(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The BENCHMARK.json metrics a run of ``cell`` reports: end-to-end
    ones without ``--trace``, per-layer ones with it."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench_dir: Path = HERE):
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"metric {name!r} has no reader at {path}")
    return load_module(path).read


def peaks(kind: str, bench_dir: Path = HERE) -> dict:
    table = load_json(bench_dir / "peaks.json")
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json "
                         f"(known: {sorted(table)})")
    return table[kind]


# ------------------------------------------------------------ the run ----

@dataclass
class Run:
    """What one run measured; the metric readers read these fields."""
    cell: Cell
    seed: int
    seconds: float
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    completed_in_window: int = 0
    trace: object = None            # tracereduce.Trace on --trace 1 runs
    device: dict = field(default_factory=dict)
    peaks: dict = field(default_factory=dict)
    notes: dict = field(default_factory=dict)


def mark(run: Run, phase: str) -> None:
    """Note when a phase of set-up ended, in seconds since the start."""
    run.notes.setdefault("phases", {})[phase] = round(
        time.perf_counter() - run.notes["t_start"], 3)


class CompileCounter:
    """Counts traces and compiles (persistent-cache loads included) while
    armed, through JAX's monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.counts = {e: 0 for e in self.EVENTS}
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.counts:
            self.counts[event] += 1
            self.names.append(kw.get("fun_name", "?"))

    @property
    def total(self) -> int:
        return sum(self.counts.values())


# ------------------------------------------------------- library loop ----

def _assess_once(X, kwargs, annotate):
    from repro import FastVAT
    with annotate("bench.fit"):
        fv = FastVAT(**kwargs).fit(X)
    with annotate("bench.assess"):
        rep = fv.assess()
    return fv, dict(rep)


def library_loop(run: Run, X, window_seconds: float, annotate, counter):
    """Assessments back to back by one caller for the whole window."""
    kwargs = dict(run.cell.config["fit"])
    for _ in range(2):                          # load every program
        fv, _ = _assess_once(X, kwargs, annotate)
    mark(run, "warm")
    if fv.method_resolved != run.cell.config["expect_method"]:
        raise BenchError(f"auto picked {fv.method_resolved!r}, the "
                         f"configuration expects "
                         f"{run.cell.config['expect_method']!r}")
    answers = []
    run.setup_s = time.perf_counter() - run.notes["t_start"]
    counter.armed = True
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while True:
            run.attempted += 1
            try:
                answers.append(_assess_once(X, kwargs, annotate))
            except Exception as exc:  # noqa: BLE001 — count, then report
                run.failed += 1
                run.notes.setdefault("errors", []).append(repr(exc))
            if time.perf_counter() - t0 >= window_seconds:
                break
    run.window_s = time.perf_counter() - t0
    counter.armed = False
    run.completed_in_window = len(answers)
    return answers


def library_check(run: Run, X, answers) -> dict:
    """Compare the window's answers with the reference."""
    import reference as ref
    metric = run.cell.config["metric"]
    fv, rep = answers[0]
    res = fv.result
    order = np.asarray(res.order)
    nums = {"permutation": 0.0 if ref.is_permutation(order, X.shape[0])
            else 1.0}
    # every answer of the window is the same computation on the same
    # table: each must repeat the first bit for bit
    same = all(np.array_equal(np.asarray(f.result.order), order)
               and np.array_equal(np.asarray(f.result.rstar),
                                  np.asarray(res.rstar))
               and r == rep for f, r in answers[1:])
    nums["repeat"] = 0.0 if same else 1.0
    if nums["permutation"]:
        return nums
    nums.update(ref.check_band(X, order, res.rstar, res.ivat_image,
                               res.sample_idx, res.group_sizes, metric))
    sizes, mids = ref.band_groups(X.shape[0], np.asarray(res.rstar).shape[0])
    R = ref.self_dist64(X[order[mids]], metric)
    nums.update(ref.check_report(rep, R))
    if fv.method_resolved == "approx":
        nums.update(ref.approx_order_numbers(
            X, order, metric, float(res.meta.approx.mst_weight)))
    else:
        nums["order_gap"] = ref.prim_gap_device(X, order, metric)
    return nums


LOOPS = {"library": (library_loop, library_check)}


# ------------------------------------------------------------ devices ----

def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — backend without memory stats
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def require_chip(chips: int) -> None:
    """Refuse to run without a TPU, or with another number of chips than
    the cell asks for (a one-chip cell must not shard silently)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) != chips:
        raise BenchError(f"the cell asks for {chips} chip(s), JAX sees "
                         f"{len(devs)}")


# ------------------------------------------------------------ tracing ----

class NoSpan:
    """Stands in for ``jax.profiler.TraceAnnotation`` when not tracing."""

    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def traced(fn):
    """Run fn(annotate) under the profiler; returns (fn's result, Trace)."""
    import jax
    import tracereduce
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as d:
        with jax.profiler.trace(d, profiler_options=opts):
            out = fn(jax.profiler.TraceAnnotation)
        files = sorted(Path(d).rglob("*.xplane.pb"))
        if not files:
            raise BenchError("the profiler wrote no trace")
        tr = tracereduce.collect(jax.profiler.ProfileData.from_file(
            str(files[-1])))
    return out, tr


# -------------------------------------------------------------- entry ----

def execute(name: str, seed: int, seconds: float, trace: bool, *,
            spec: dict, t_start: float, bench_dir: Path = HERE,
            chip_check: bool = True, log=None, phases=None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``phases`` holds set-up phases the caller timed before this call
    (seconds since ``t_start``); the run adds its own and logs them all.
    """
    import traffic
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = load_cell(name, bench_dir)
    entries = metric_entries(spec, name, trace)
    readers = {m["name"]: reader(m["name"], bench_dir) for m in entries}
    run = Run(cell=cell, seed=seed, seconds=seconds,
              notes={"t_start": t_start, "phases": dict(phases or {})})
    if chip_check:
        require_chip(cell.chips)
    mark(run, "devices")
    counter = CompileCounter()
    X = traffic.table(cell.config, seed)
    mark(run, "table")
    loop, check = LOOPS[cell.loop]
    window = min(seconds, cell.trace_seconds) if trace else seconds
    if trace:
        answers, run.trace = traced(
            lambda annotate: loop(run, X, window, annotate, counter))
    else:
        answers = loop(run, X, window, NoSpan, counter)
    run.device = device_info(cell.chips)
    try:
        run.peaks = peaks(run.device["kind"], bench_dir)
    except BenchError:
        if chip_check:
            raise
    nums = check(run, X, answers)
    nums["window_compiles"] = float(counter.total)
    checks = {}
    correct = run.failed == 0 and bool(answers)
    for key, value in nums.items():
        limit = float(cell.limits.get(key, 0.0))
        checks[key] = {"value": value, "limit": limit}
        if not value <= limit:
            correct = False
    metrics = {}
    for m in entries:
        v = readers[m["name"]](run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if counter.names:
        log(f"compiled in the window: {sorted(set(counter.names))}")
    log(f"set-up phases, seconds since start: {run.notes['phases']}, "
        f"window opened at {run.setup_s!r}")
    log(f"window {run.window_s!r} s, attempted {run.attempted}, failed "
        f"{run.failed}, completed in window {run.completed_in_window}, "
        f"window compiles {nums['window_compiles']!r}")
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics,
              "device": dict(run.device)}
    if trace and run.trace is not None:
        import tracereduce
        log(f"programs in the traced window: {tracereduce.top_programs(run.trace)}")
        got = tracereduce.device_busy(run.trace)
        if got is not None:
            result["device"]["busy_s"], result["device"]["window_s"] = got
        result["breakdown"] = {
            "device_ops": tracereduce.top_ops(run.trace),
            "idle_gaps": tracereduce.idle_gaps(run.trace)}
    for key, c in checks.items():
        log(f"check {key} {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result
