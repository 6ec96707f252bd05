"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
    PYTHONPATH=src python -m benchmarks.run --check   # regression gate

``--check`` re-measures the throughput benches and compares each
steps/s entry against the committed ``results/bench/*.json`` baselines,
failing on a >30% regression; the baseline files are restored afterwards
so the gate is side-effect-free (``make bench-check``).

Prints ``name,us_per_call,derived`` CSV lines (derived is a JSON dict).
Mapping to the paper:
    simulator_throughput  Fig. 3/5 middle (GS vs IALS total runtime)
    multi_agent_throughput  Distributed-IALS: N batched IALS vs Python loop
    aip_accuracy          Fig. 3/5 bottom + App. E Eq. 9/10
    learning_curves       Fig. 3/5 top + App. E Fig. 11/12 (F-IALS)
    serve_throughput      continuous-batching policy serving: QPS + p50/p99
    fleet_throughput      disaggregated actor/learner scaling + faults
    memory_dependence     Fig. 6 (Theorem 1)
    dset_ablation         App. B / §4.2 (Theorem 2)
    kernel_bench          Pallas kernels vs oracles
    roofline_report       EXPERIMENTS.md §Roofline source (dry-run cells)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

MODULES = [
    "kernel_bench",
    "roofline_report",
    "simulator_throughput",
    "multi_agent_throughput",
    "train_throughput",
    "serve_throughput",
    "fleet_throughput",
    "aip_accuracy",
    "dset_ablation",
    "memory_dependence",
    "learning_curves",
]

# modules whose saved JSONs are flat {simulator: steps/s} rate tables —
# the --check regression gate compares these against the committed files
CHECK_MODULES = {"simulator_throughput": "sim_throughput_",
                 "multi_agent_throughput": "multi_agent_throughput_",
                 "train_throughput": "train_throughput_",
                 "serve_throughput": "serve_throughput_",
                 # fleet_faults_*.json is informational, not a baseline —
                 # the prefix below deliberately excludes it
                 "fleet_throughput": "fleet_throughput_"}
CHECK_TOLERANCE = 0.30


def _rate_files(mods):
    from .common import RESULTS_DIR
    prefixes = tuple(CHECK_MODULES[m] for m in mods)
    return sorted(p for p in RESULTS_DIR.glob("*.json")
                  if p.name.startswith(prefixes))


def check_dryrun_cells() -> int:
    """The roofline pipeline must have real cells: zero ok dry-run cells
    means the committed roofline artifacts are (or would regenerate as)
    empty — fail the gate and name the command that fixes it."""
    from . import roofline_report
    cells = roofline_report.load_cells()
    ok = [c for c in cells if c.get("status") == "ok"]
    if not ok:
        print(f"# check: {len(cells)} dry-run cells, 0 ok — the roofline "
              f"artifacts are empty. Run: {roofline_report.DRYRUN_CMD}")
        return 1
    print(f"# check: roofline dry-run cells ok={len(ok)}")
    return 0


def check_regressions(baselines) -> int:
    """Compare freshly saved rate tables against the committed baselines.
    -> number of >CHECK_TOLERANCE regressions (0 == gate passes)."""
    bad = 0
    for path, old in baselines.items():
        new = json.loads(path.read_text())
        for sim, old_rate in old.items():
            new_rate = new.get(sim)
            if new_rate is None:
                print(f"# check: {path.name}:{sim} missing from fresh run")
                bad += 1
                continue
            ratio = new_rate / max(old_rate, 1e-9)
            status = "REGRESSION" if ratio < 1.0 - CHECK_TOLERANCE else "ok"
            print(f"# check: {path.name}:{sim} {old_rate:.0f} -> "
                  f"{new_rate:.0f} steps/s ({ratio:.2f}x) {status}")
            if status == "REGRESSION":
                bad += 1
    return bad


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--check", action="store_true",
                    help="re-measure throughput benches, fail on a >30%% "
                         "steps/s regression vs results/bench baselines")
    ap.add_argument("--out", default=None,
                    help="write every module's emitted rows to this JSON "
                         "file (CI uploads it as the bench-smoke "
                         "artifact); never touches results/bench")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.check:
        if args.quick:
            ap.error("--check needs full-size runs (the baselines were "
                     "measured at full size); drop --quick")
        mods = [m for m in CHECK_MODULES
                if args.only is None or m == args.only]
        if not mods:
            ap.error(f"--check --only must name one of "
                     f"{sorted(CHECK_MODULES)}")
    else:
        mods = [m for m in MODULES if args.only is None or m == args.only]
    baselines = ({p: json.loads(p.read_text()) for p in _rate_files(mods)}
                 if args.check else {})

    print("name,us_per_call,derived")
    failures = 0
    collected = {}
    try:
        for name in mods:
            t0 = time.time()
            print(f"# --- {name} ---", flush=True)
            try:
                mod = __import__(f"benchmarks.{name}", fromlist=["run"])
                collected[name] = mod.run(quick=args.quick)
                print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
            except Exception:
                failures += 1
                print(f"# {name} FAILED:", flush=True)
                traceback.print_exc()
        if args.check:
            failures += check_regressions(baselines)
            failures += check_dryrun_cells()
    finally:
        for path, old in baselines.items():   # gate is side-effect-free,
            path.write_text(json.dumps(old, indent=1))  # crash included
        if args.out:
            from pathlib import Path
            Path(args.out).write_text(json.dumps(
                {"quick": args.quick, "failures": failures,
                 "rows": collected}, indent=1))
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
