#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/mixes/<traffic>.json``, whose ``kind`` picks the training or the
serving runner); ``correct`` is held to ``bench/limits/<cell>.json``;
each per-layer metric is read by ``bench/metrics/<metric>.py``.

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1`` runs
the same window under the profiler and reports its per-layer metrics.
Both check the timed path's outputs against the plain reference
(``bench/reference``) after the window. The last line of standard output
is the result object; the numbers compared, each beside its limit, end
standard error and the result line. With no TPU, or fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import cells, device, tracing, verdict  # noqa: E402

NOT_SERVED_MS = 1e9        # the latency a request that never completed gets


def settle_heap():
    """End set-up: collect, then freeze what set-up left alive out of the
    cyclic collector's reach. The serving window's whole open-loop trace
    is built ahead (hundreds of thousands of requests that a deployment
    would receive over time, never hold at once); left to the collector,
    each full collection scans it and stalls the host for about a tenth
    of a second inside the window. What the window itself allocates is
    collected as usual."""
    gc.collect()
    gc.freeze()


def _train(cell, cfg, mix, args, devices, clock):
    import jax.numpy as jnp
    from bench.lib import train
    prog = train.Program(cfg, mix, devices)
    state = prog.start(args.seed)
    keys = train.iteration_keys(args.seed, 0,
                                train.CHECKED + mix["max_iterations"])
    state, got = train.checked_steps(prog, state, keys[:train.CHECKED])
    lag = train.read_lag(mix, got["seconds"][-1])
    settle_heap()
    setup_s = time.perf_counter() - T_START
    compiles = clock.count
    red = None
    with tempfile.TemporaryDirectory() as tmp:
        if args.trace:
            with tracing.capture(tmp) as path:
                state, n, failed, elapsed = train.window(
                    prog, state, keys[train.CHECKED:], args.seconds, lag)
            red = tracing.reduce(tracing.load(path[0]), len(devices))
        else:
            state, n, failed, elapsed = train.window(
                prog, state, keys[train.CHECKED:], args.seconds, lag)
    window_compiles = clock.count - compiles
    dev = device.info(devices)
    del state
    ref = train.reference_steps(cfg, mix, args.seed, got["p0"], prog.aip,
                                jnp.float32)
    run = {"kind": "train", "cfg": cfg, "mix": mix, "chips": len(devices),
           "trace": red, "iterations": n, "window_s": elapsed,
           "read_lag": lag,
           "samples_per_s": n * prog.samples_per_iteration / elapsed,
           "peaks": device.peaks(dev["kind"]) if args.trace else None}
    e2e = {"setup_s": setup_s,
           "train_samples_per_s": run["samples_per_s"]}
    return dict(attempted=n, failed=failed, e2e=e2e, run=run, dev=dev,
                checks=train.readings(got, ref),
                window_compiles=window_compiles)


def _serve(cell, cfg, mix, args, devices, clock):
    import jax.numpy as jnp
    import numpy as np
    from bench.lib import serve
    srv = serve.Server(cfg, mix, args.seed)
    srv.replay(serve.trace_for(cfg, mix, args.seed + 1, mix["warm_s"]))
    tr = serve.trace_for(cfg, mix, args.seed, args.seconds)
    reqs = serve.program_requests(tr)
    settle_heap()
    setup_s = time.perf_counter() - T_START
    compiles = clock.count
    red = None
    with tempfile.TemporaryDirectory() as tmp:
        if args.trace:
            with tracing.capture(tmp) as path:
                res = srv.replay(tr, reqs)
            red = tracing.reduce(tracing.load(path[0]), len(devices))
        else:
            res = srv.replay(tr, reqs)
    window_compiles = clock.count - compiles
    dev = device.info(devices)
    lat_ms = np.where(np.isfinite(res["latency_s"]),
                      res["latency_s"] * 1e3, NOT_SERVED_MS)
    n = len(lat_ms)
    failed = int(np.sum(~np.isfinite(res["latency_s"])))
    stats = res["stats"]
    rng = np.random.default_rng([args.seed, 4])
    served = np.flatnonzero(np.isfinite(res["latency_s"]))
    idx = np.sort(rng.choice(served, size=min(len(served),
                                              mix["check_sample"]),
                             replace=False))
    acts, logits = srv.served_outputs(res["where"], idx)
    srv.outs = []
    ref = serve.reference_logits(cfg, srv.params, tr["frame"][idx],
                                 jnp.float32)
    run = {"kind": "serve", "cfg": cfg, "mix": mix, "chips": len(devices),
           "trace": red, "dispatches": stats.dispatches,
           "real_lanes": stats.real_lanes,
           "latency_ms": {"p99": float(np.percentile(lat_ms, 99))},
           "peaks": device.peaks(dev["kind"]) if args.trace else None}
    e2e = {"setup_s": setup_s,
           "serve_p50_ms": float(np.percentile(lat_ms, 50))}
    return dict(attempted=n, failed=failed, e2e=e2e, run=run, dev=dev,
                checks=serve.readings(acts, logits, ref),
                window_compiles=window_compiles)


RUNNERS = {"train": _train, "serve": _serve}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.cell(args.workload)
    cfg, mix = cells.config(cell["config"]), cells.mix(cell["traffic"])
    limits = cells.limits(cell["name"])
    try:
        devices = device.require_chips(cell["chips"])
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    device.enable_compile_cache()
    from bench.lib.clock import CompileClock
    clock = CompileClock()
    out = RUNNERS[mix["kind"]](cell, cfg, mix, args, devices, clock)

    if args.trace:
        metrics = {}
        for m in cells.metrics_of(cell["name"], "per_layer"):
            v = cells.reader(m["name"])(out["run"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in cells.metrics_of(cell["name"], "end_to_end")}
    correct, checks = verdict.judge(out["checks"], limits, out["failed"])
    dev = out["dev"]
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": dev}
    red = out["run"]["trace"]
    if args.trace and red:
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["window_compiles"] = out["window_compiles"]
    result["checks"] = checks
    print(f"bench: {out['window_compiles']} compiles inside the window, "
          f"{clock.hits} persistent cache hits", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
