"""The benchmark's driver: resolve a cell by name, check the device, run the
cell's entry kind, read its metrics, and print the result line.

Everything that belongs to one configuration, traffic mix, entry kind or
per-layer metric sits in a file of its own under this directory, found by
name from ``BENCHMARK.json``:

- ``configs/<config>.json`` (the file the configuration entry names);
- ``traffic/<traffic>.json``: the load, and under ``entry`` the entry kind;
- ``entries/<entry>.py``: ``run(job) -> dict`` drives the program's normal
  path for that kind, measures the window and checks the results;
- ``metrics/<name>.py``: ``read(view) -> float | None`` takes one per-layer
  metric from the trace of a ``--trace 1`` run.

A metric named ``<quantity>.<cells>`` (a quantity split by the end-to-end
metric its cells report, as ``lane_tick_ns.single``) is that quantity: its
reader is ``metrics/<name>.py`` where that file exists, else
``metrics/<quantity>.py``, and an end-to-end one takes the value the entry
reports as ``<quantity>``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".perfbench_out")


class WindowClosed(Exception):
    """Raised from the program's callbacks once the measured window has
    closed, to stop feeding it."""


# ---------------------------------------------------------------------------
# resolving a cell
# ---------------------------------------------------------------------------
def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, workload: str,
            e2e_names: Optional[List[str]] = None) -> bool:
    """Whether a cell reports ``metric``: the cells its ``workloads`` key
    lists, else every cell (an end-to-end metric) or every cell that reports
    the end-to-end metric it ``moves`` (a per-layer metric)."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def quantity(name: str) -> str:
    """The quantity a metric name measures: ``lane_tick_ns.single`` ->
    ``lane_tick_ns``."""
    return name.split(".", 1)[0]


def reader_path(name: str) -> str:
    """The reader of per-layer metric ``name``: a file of its own, else the
    reader of its quantity."""
    for stem in (name, quantity(name)):
        path = os.path.join(HERE, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no reader metrics/{name}.py or "
                            f"metrics/{quantity(name)}.py")


class Cell:
    """One entry of ``workloads`` with its configuration, traffic, entry
    kind and metrics resolved from files under this directory."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.workload = cells[name]
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        with open(os.path.join(root, self.config_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic_name = self.workload["traffic"]
        with open(os.path.join(HERE, "traffic",
                               f"{self.traffic_name}.json")) as f:
            self.traffic = json.load(f)
        self.entry_path = os.path.join(HERE, "entries",
                                       f"{self.traffic['entry']}.py")
        if not os.path.exists(self.entry_path):
            raise FileNotFoundError(self.entry_path)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if applies(m, name)]
        names = [m["name"] for m in self.end_to_end]
        self.per_layer = [m for m in bench["per_layer"]
                          if applies(m, name, names)]
        self.readers = {m["name"]: reader_path(m["name"])
                        for m in self.per_layer}

    def entry(self):
        return load_module(self.entry_path, f"perfbench_entry_"
                           f"{self.traffic['entry']}")


# ---------------------------------------------------------------------------
# measuring
# ---------------------------------------------------------------------------
class Tracer:
    """The profiler (``--trace 1`` only): it starts as the measured window
    opens, marks the window as the host span ``bench.window``, and stops
    once the programs in flight when the window closed have ended, so that
    those whole programs are in the trace too."""

    def __init__(self, enabled: bool, directory: str):
        self.enabled = enabled
        self.directory = directory
        self._span = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()

    def end_window(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def stop(self) -> None:
        if self.enabled:
            import jax
            self.end_window()
            jax.profiler.stop_trace()
            self.enabled = False


class Window:
    """The measured window: opens at a completion after warm-up and closes
    at the first completion at or after ``seconds`` past the opening. Counts
    the programs compiled while it is open (there should be none)."""

    def __init__(self, seconds: float, tracer: Tracer):
        self.seconds = seconds
        self.tracer = tracer
        self.open_t: Optional[float] = None
        self.close_t: Optional[float] = None
        self.compiled: List[str] = []
        self._lock = threading.Lock()

    def note_compile(self, fun_name: str) -> None:
        """A program was compiled or loaded from the persistent cache."""
        with self._lock:
            if self.open_t is not None and self.close_t is None:
                self.compiled.append(fun_name)

    def open(self, t: float) -> None:
        self.open_t = t
        self.tracer.start()

    def due(self, t: float) -> bool:
        return self.open_t is not None and t >= self.open_t + self.seconds

    def close(self, t: float) -> None:
        self.close_t = t
        self.tracer.end_window()

    @property
    def length(self) -> float:
        return self.close_t - self.open_t


class Job:
    """What an entry needs to run one cell once."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices: list, t0: float, work_dir: str):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.t0 = t0
        self.work_dir = work_dir
        self.window = Window(seconds, Tracer(trace, os.path.join(
            work_dir, "trace")))
        self.memory_peak_bytes = 0

    def drained(self) -> None:
        """Call once the window has closed and the run stopped feeding the
        chips: waits until every program already on them has ended, reads
        the peak device memory of the fullest chip and stops the profiler,
        all before the reference runs."""
        import jax
        import jax.numpy as jnp
        for d in self.devices:        # a chip runs its programs in order
            jax.block_until_ready(jax.device_put(jnp.zeros(()), d) + 1)
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = peak
        self.window.tracer.stop()

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_device(chips: int) -> dict:
    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", file=sys.stderr, flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"perfbench: JAX found no TPU (platform "
                         f"{info['platform']!r}); nothing was measured")
    if info["count"] < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chips, JAX "
                         f"found {info['count']}")
    return info


def enable_cache() -> str:
    """The program's compilation cache (its own directory choice: the
    ``JAX_COMPILATION_CACHE_DIR`` it is given, else a fixed path in the
    checkout), keeping every program however quickly it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------
class View:
    """What a per-layer reader sees: the traced window on the cell's chips,
    the benchmark's host spans, and facts the entry states about its
    programs (module names, lanes, ticks, bytes and operations)."""

    def __init__(self, trace, chips: int, facts: dict, device_kind: str):
        from trace_reduce import within
        self.trace = trace
        self.facts = facts
        self.device_kind = device_kind
        bounds = trace.span("bench.window")
        if bounds is None:
            raise RuntimeError("the trace holds no bench.window span")
        self.lo, self.hi = bounds
        self.planes = sorted(trace.devices, key=lambda p: int(
            p.rsplit(":", 1)[1]))[:chips]
        self._within = within

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def ops(self, plane: str):
        from trace_reduce import clip
        return clip(self.trace.devices[plane]["ops"], self.lo, self.hi)

    def modules(self, plane: str, name: Optional[str] = None):
        """Program executions that started once the window had opened (the
        trace holds them whole: it stops after the last one ended)."""
        return [m for m in self.trace.devices[plane]["modules"]
                if m[1] >= self.lo and (name is None or m[0] == name)]

    def ops_in(self, plane: str, modules) -> list:
        """Operations that ran inside the given program executions."""
        out = []
        ops = self.trace.devices[plane]["ops"]
        for _, s, d in modules:
            out.extend(self._within(ops, s, s + d))
        return out

    def busy_s(self) -> float:
        from trace_reduce import busy_ns
        vals = [busy_ns(self.trace.devices[p]["ops"], self.lo, self.hi)
                for p in self.planes]
        return sum(vals) / len(vals) / 1e9


def breakdown(view: View) -> dict:
    from trace_reduce import attribute, idle_gaps, op_totals, top
    ops: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for p in view.planes:
        for n, v in op_totals(view.ops(p), skip=("%while",
                                                 "%conditional")).items():
            ops[n] = ops.get(n, 0.0) + v / 1e9
        g = idle_gaps(view.trace.devices[p]["ops"], view.lo, view.hi)
        for n, v in attribute(g, view.trace.spans).items():
            gaps[n] = gaps.get(n, 0.0) + v / 1e9
    return {"device_ops": top(ops), "idle_gaps": top(gaps)}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def finite(x: float) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, t0: float,
             require_tpu: bool = True, out_dir: str = OUT,
             compile_cache: bool = True) -> dict:
    """Run ``cell`` once and return its result object (the line printed).
    The tests run cells on the CPU with ``require_tpu=False`` (no look for
    a chip), their own ``out_dir`` and JAX's default (no) compile cache."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    if compile_cache:
        enable_cache()
    info = check_device(cell.chips) if require_tpu else device_info()
    devices = jax.devices()[:cell.chips]
    work = os.path.join(out_dir, cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work, exist_ok=True)
    job = Job(cell, seed, seconds, trace, devices, t0, work)
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **kw: job.window.note_compile(
            str(kw.get("fun_name")))
        if event == "/jax/core/compile/backend_compile_duration" else None)

    out = cell.entry().run(job)

    w = job.window
    e2e = dict(out["e2e"])
    e2e["setup_s"] = w.open_t - t0
    checks = out["checks"]
    correct = all(finite(v) and v <= lim for _, v, lim in checks)
    device = dict(info, memory_peak_bytes=job.memory_peak_bytes)
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": int(out["attempted"]),
                              "failed": int(out["failed"])}
    notes = [f"window {w.length:.3f} s, {len(w.compiled)} programs compiled "
             f"inside it {w.compiled}"] + list(out.get("notes", ()))
    if trace:
        from trace_reduce import Trace
        view = View(Trace.from_dir(job.window.tracer.directory), cell.chips,
                    out.get("facts", {}), info["kind"])
        metrics = {}
        for m in cell.per_layer:
            value = load_module(cell.readers[m["name"]],
                                "perfbench_metric").read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=view.busy_s(), window_s=view.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = breakdown(view)
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e.get(m["name"], e2e.get(
                quantity(m["name"]))), "unit": m["unit"]}
            for m in cell.end_to_end}
        result["device"] = device
    # a number that is not finite (nothing to compare) prints as null
    result["checks"] = {n: {"value": v if finite(v) else None, "limit": lim}
                        for n, v, lim in checks}
    result["_notes"] = notes
    return result


def main(argv: List[str], t0: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="perfbench: one cell, one run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(load_benchmark(), args.workload)
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t0=t0)
    except SystemExit as e:
        print(e, file=sys.stderr, flush=True)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    for note in result.pop("_notes"):
        print(note, file=sys.stderr)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] is not None and \
            c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
