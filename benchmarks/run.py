"""Benchmark harness: one module per paper table/figure.

Usage:
  PYTHONPATH=src python -m benchmarks.run [--quick|--full] [--only NAME[,NAME]]
      [--repeats N]

Output: ``name,us_per_call,derived`` CSV rows (stdout), one per measurement,
plus a machine-readable ``BENCH_<date>.json`` at the repo root (suite
wall-times as min/median/IQR over ``--repeats`` trials, throughput rows,
device kind, git sha) for run-over-run comparison.  Every report is also
appended to the ``experiments/bench_history/`` store, which the regression
sentinel (``python -m repro.obs.regress``) compares against the committed
baselines under ``benchmarks/baselines/``.  Roofline/dry-run numbers live in
experiments/dryrun (see EXPERIMENTS.md).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback

from .common import BenchCtx, emit

BENCHES = [
    "dataset",        # Figs. 5/7/8
    "correlation",    # Figs. 1/9
    "pr",             # Figs. 2/10
    "estimators",     # Table 3
    "map",            # Fig. 11
    "dse",            # Figs. 12/13
    "sota",           # Figs. 14/15
    "apps",           # Figs. 16-19
    "kernels",        # beyond-paper kernel parity
    "fastchar",       # batched characterization engine vs numpy oracle
    "fastapp",        # batched application-BEHAV engine vs numpy oracle
    "tablefree",      # entry-synthesized engines vs table-build + 12-bit sampled
    "fastmoo",        # device NSGA-II engine vs numpy oracle GA
    "shard",          # multi-device ExecutionContext scaling (forced host devs)
    "serving",        # AxO-deployed LM serving: tokens/sec vs rank vs BEHAV
    "service",        # persistent DSE service: cold vs warm library, queue
]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except Exception:
        return "unknown"


def _device_kind() -> str:
    try:
        import jax

        dev = jax.devices()[0]
        return f"{dev.platform}:{getattr(dev, 'device_kind', '?')}x{jax.device_count()}"
    except Exception:
        return "unknown"


def write_report(report: dict, out_dir: str = REPO_ROOT) -> str:
    """Write ``BENCH_<YYYY-MM-DD>.json`` (UTC date) and return its path."""
    date = time.strftime("%Y-%m-%d", time.gmtime())
    path = os.path.join(out_dir, f"BENCH_{date}.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (250 GA generations, full grids)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeats", type=int, default=3,
                    help="trials per suite; wall-time reports min/median/IQR "
                         "over them (first trial includes jit compiles, so "
                         "min ~= warm wall).  Default 3.")
    ap.add_argument("--no-report", action="store_true",
                    help="skip writing BENCH_<date>.json (and the history "
                         "append) -- stdout rows only")
    ap.add_argument("--no-history", action="store_true",
                    help="write the report but do not append it to "
                         "experiments/bench_history/")
    args = ap.parse_args(argv)
    repeats = max(1, args.repeats)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    # provenance captured once per run, stamped into every suite entry (the
    # regression sentinel refuses to reason about rows with no origin)
    git_sha = _git_sha()
    device = _device_kind()

    ctx = BenchCtx(quick=not args.full, seed=args.seed)
    names = args.only.split(",") if args.only else BENCHES
    print("name,us_per_call,derived")
    failures = 0
    suites: dict[str, dict] = {}
    t_start = time.perf_counter()
    for name in names:
        mod_name = f"benchmarks.bench_{name}"
        walls: list[float] = []
        rows: list[dict] = []
        try:
            mod = __import__(mod_name, fromlist=["run"])
            for rep in range(repeats):
                t0 = time.perf_counter()
                rows = mod.run(ctx)
                walls.append(time.perf_counter() - t0)
                if rep == 0:
                    emit(rows)  # rows are deterministic: print the first trial
            from repro.obs.regress import wall_stats

            entry = wall_stats(walls)
            entry.update({
                "rows": rows,
                "git_sha": git_sha,
                "device": device,
                "repeats": len(walls),
            })
            suites[name] = entry
            print(f"# bench_{name}: {len(rows)} rows, wall "
                  f"min={entry['wall_s_min']:.1f}s "
                  f"median={entry['wall_s_median']:.1f}s "
                  f"iqr={entry['wall_s_iqr']:.2f}s over {len(walls)} trials",
                  flush=True)
        except Exception:
            traceback.print_exc()
            print(f"# bench_{name}: FAILED", flush=True)
            suites[name] = {"wall_s": round(sum(walls), 3), "failed": True,
                            "git_sha": git_sha, "device": device,
                            "repeats": len(walls)}
            failures += 1

    if not args.no_report:
        report = {
            "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "git_sha": git_sha,
            "device": device,
            "quick": not args.full,
            "seed": args.seed,
            "repeats": repeats,
            "total_wall_s": round(time.perf_counter() - t_start, 3),
            "failures": failures,
            "suites": suites,
        }
        path = write_report(report)
        print(f"# report: {path}", flush=True)
        if not args.no_history:
            from repro.obs.regress import append_history

            hist = append_history(report)
            print(f"# history: {hist}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
