#!/usr/bin/env python3
"""Sweep a serving cell's offered rate once, on the chip, to find the
highest rate the server sustains (the knee its mix file's ``rate_rps``
is set from, at 0.8 of it).

    python3 bench/knee.py --workload traffic25_fnn.serve_r80 \
        --rates 20000,40000,60000 --seconds 4 --seed 7

A rate is sustained when the requests due in the window are all served
within ``--drain`` seconds of its close (the queue does not grow over
the window). One process serves every rate on one server.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import cells, device, serve  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--drain", type=float, default=0.05)
    args = ap.parse_args(argv)
    cell = cells.cell(args.workload)
    cfg, mix = cells.config(cell["config"]), cells.mix(cell["traffic"])
    try:
        device.require_chips(cell["chips"])
    except device.NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return 2
    device.enable_compile_cache()
    import numpy as np
    srv = serve.Server(cfg, mix, args.seed)
    srv.replay(serve.trace_for(cfg, mix, args.seed + 1, mix["warm_s"]))
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        tr = serve.trace_for(cfg, mix, args.seed, args.seconds, rate=rate)
        reqs = serve.program_requests(tr)
        gc.collect()
        gc.freeze()              # as a run's set-up ends (run.settle_heap)
        res = srv.replay(tr, reqs)
        srv.outs = []
        del reqs
        gc.unfreeze()
        lat = res["latency_s"]
        done = tr["arrival"] + lat
        st = res["stats"]
        row = {"rate_rps": rate, "requests": len(lat),
               "served_rps": len(lat) / max(done.max(), 1e-9),
               "p50_ms": float(np.percentile(lat, 50) * 1e3),
               "p99_ms": float(np.percentile(lat, 99) * 1e3),
               "drain_s": float(done.max() - tr["arrival"].max()),
               "lanes_per_dispatch": st.real_lanes / max(st.dispatches, 1)}
        row["sustained"] = row["drain_s"] <= args.drain
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["rate_rps"] for r in rows if r["sustained"]]
    print(json.dumps({"knee_rps": max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
