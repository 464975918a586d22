"""The chip benchmark: run one cell of BENCHMARK.json and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``, its
configuration file, its traffic file ``bench/traffic/<traffic>.json`` (whose
``kind`` names the driver ``bench/drivers/<kind>.py``), the plain reference
``bench/reference/<config>.py`` and, with ``--trace 1``, one reader
``bench/metrics/<metric>.py`` per per-layer metric.  A serving driver builds
the model through its family ``bench/models/<model_type>.py``.  Adding a
configuration, a model family, a cell or a metric adds files and entries;
nothing here changes.

A run sets up (weights or dataset from the seed, every shape of the cell's
traffic warmed), measures for ``--seconds``, then checks what the timed path
produced against the plain reference.  The last lines of stderr are the
numbers compared, each beside its limit; the last line of stdout is one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` when traced).  Without a TPU, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# JAX's persistent compilation cache, unless the environment names one: a
# fixed directory inside the checkout, so only a cell's first run compiles.
CACHE_DIR = ROOT / ".bench_cache" / "jax_compile"
TRACE_DIR = ROOT / ".bench_cache" / "trace"


def load_module(path: Path, name: str | None = None):
    """Import a benchmark file by path (names may hold '.' and '-')."""
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names, resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    reference: Path

    @property
    def driver(self) -> Path:
        return BENCH / "drivers" / f"{self.traffic['kind']}.py"


def resolve_cell(name: str, bench: dict | Path = ROOT / "BENCHMARK.json") -> Cell:
    """Resolve a cell's configuration, traffic, reference and metric files
    from ``BENCHMARK.json`` (or a document in its format)."""
    if not isinstance(bench, dict):
        bench = load_json(bench)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", cells)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if m["moves"] in moved and name in m.get("workloads", cells)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(ROOT / cfg["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        end_to_end=e2e, per_layer=layer,
        reference=BENCH / "reference" / f"{w['config']}.py",
    )


@dataclass
class Check:
    """One number compared with the reference, and its limit (pass: <=)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Run:
    """What a driver reads and records during one run.

    ``span`` records a host span of the benchmark's own (and, when tracing,
    a ``TraceAnnotation`` on the profiler's clock); ``open_window`` and
    ``close_window`` bracket the measured window, start and stop the
    profiler, and read the device's peak memory before any check runs.
    That peak is the process's, set-up's transients included; the drivers
    call ``note_memory`` where the window's own state is largest, and
    ``memory_window_bytes`` is the most in use at those points.
    """

    seed: int
    seconds: float
    trace: bool
    spans: list = field(default_factory=list)
    setup_s: float | None = None
    window_s: float | None = None
    t_window: float | None = None
    memory_peak_bytes: int | None = None
    memory_window_bytes: int = 0
    trace_path: Path | None = None
    compiles: list = field(default_factory=list)
    cache_loads: int = 0
    compiles_in_window: list | None = None
    cache_loads_in_window: int | None = None

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        if self.trace:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def on_compile(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(str(kw.get("fun_name", "?")))

    def on_event(self, event: str, **kw) -> None:
        # a program found in the persistent cache still passes through the
        # compile event above; it is loaded, not compiled
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1

    def note_memory(self) -> None:
        """Record the bytes in use on the fullest chip, inside the window."""
        import jax

        if self.t_window is None or self.window_s is not None:
            return
        used = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.local_devices())
        self.memory_window_bytes = max(self.memory_window_bytes, used)

    def open_window(self) -> None:
        import jax

        self.setup_s = time.perf_counter() - PROCESS_T0
        self._compiles0, self._loads0 = len(self.compiles), self.cache_loads
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # host spans come from annotations
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.t_window = time.perf_counter()

    def close_window(self, t_end: float | None = None) -> None:
        import jax

        t_end = time.perf_counter() if t_end is None else t_end
        self.note_memory()
        self.window_s = t_end - self.t_window
        if self.trace:
            jax.profiler.stop_trace()
            found = sorted(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
            self.trace_path = found[-1] if found else None
        self.compiles_in_window = self.compiles[self._compiles0:]
        self.cache_loads_in_window = self.cache_loads - self._loads0
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        self.memory_peak_bytes = max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0)


def device_info(n_chips: int) -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": n_chips}


def check_devices(chips: int) -> str | None:
    """Why this machine cannot run the cell, or None when it can."""
    import jax

    try:
        backend = jax.default_backend()
    except RuntimeError as e:  # no backend at all
        return f"JAX found no backend: {e}"
    if backend != "tpu":
        return f"needs a TPU, JAX found {backend!r}"
    if len(jax.devices()) < chips:
        return f"the cell needs {chips} chip(s), JAX sees {len(jax.devices())}"
    return None


def enable_cache() -> None:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says (JAX
    reads it itself), otherwise in the checkout's ``.bench_cache``."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def layer_metrics(cell: Cell, run: Run, out: dict, device_kind: str) -> tuple:
    """Per-layer metrics of a traced run, each from its own reader file."""
    from trace_reduce import reduce_trace  # noqa: E402  (bench/ on sys.path)

    import counts

    summary = reduce_trace(run.trace_path) if run.trace_path else None
    ctx = {"run": run, "layer": out.get("layer", {}), "trace": summary,
           "peaks": counts.peaks_for(device_kind), "config": cell.config,
           "traffic": cell.traffic}
    metrics = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics, summary


def span_summary(run: Run) -> str:
    """One line: count, total, median and longest seconds of each span
    inside the window, so a run that reads slow shows where it stalled."""
    t0, t1 = run.t_window, run.t_window + run.window_s
    parts = []
    for name in sorted({s[0] for s in run.spans}):
        d = sorted(e - s for n, s, e in run.spans
                   if n == name and s >= t0 and e <= t1)
        if d:
            parts.append(f"{name} n={len(d)} sum={sum(d):.3f} "
                         f"median={d[len(d) // 2]:.5f} max={d[-1]:.5f}")
    return "bench: window spans " + "; ".join(parts)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             driver=None) -> dict:
    """Set up, measure and check one cell; returns the result object.

    The look for a chip is ``main``'s, so the harness's own tests can drive
    a run on the CPU at a tiny size; ``driver`` replaces the module named by
    the traffic's kind (the control and the tests' planted faults).
    """
    import jax

    run = Run(seed=seed, seconds=seconds, trace=trace)
    jax.monitoring.register_event_duration_secs_listener(run.on_compile)
    jax.monitoring.register_event_listener(run.on_event)
    driver = driver or load_module(cell.driver)
    reference = load_module(cell.reference)
    out = driver.run(cell, run, reference)

    checks: list[Check] = out["checks"]
    dev = device_info(cell.chips)
    dev["memory_peak_bytes"] = int(run.memory_peak_bytes or 0)
    dev["memory_window_bytes"] = int(run.memory_window_bytes)
    result = {"correct": bool(checks) and all(c.ok for c in checks),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if trace:
        metrics, summary = layer_metrics(cell, run, out, dev["kind"])
        if summary is not None:
            dev["busy_s"] = summary.busy_s
            if summary.busy_s > 1.01 * run.window_s:
                # device and host clocks disagree, or ops are counted twice
                print(f"bench: TRACE FAULT busy_s {summary.busy_s!r} exceeds "
                      f"window_s {run.window_s!r}", file=sys.stderr)
        dev["window_s"] = run.window_s
        result["metrics"] = metrics
        result["device"] = dev
        if summary is not None:
            result["breakdown"] = summary.breakdown()
    else:
        metrics = {m["name"]: {"value": float(out["metrics"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": float(run.setup_s), "unit": "s"}
        result["metrics"] = metrics
        result["device"] = dev
    built, loads = run.compiles_in_window, run.cache_loads_in_window
    print(f"bench: setup_s {run.setup_s:.3f} window_s {run.window_s:.3f} "
          f"compiles_in_window {len(built) - loads} "
          f"cache_loads_in_window {loads} programs_built_in_window {built} "
          f"memory_peak_bytes {run.memory_peak_bytes} "
          f"memory_window_bytes {run.memory_window_bytes}", file=sys.stderr)
    print(span_summary(run), file=sys.stderr)
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = resolve_cell(args.workload)
    why = check_devices(cell.chips)
    if why:
        print(f"bench: {why}", file=sys.stderr)
        return 2
    enable_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

if __name__ == "__main__":
    sys.exit(main())
