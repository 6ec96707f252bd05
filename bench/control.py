#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 \
        [--seconds 3] [--fault-seeds 3]

For each seed, in one process, it prints one JSON row per source:
``program`` (the timed path as a run drives it, against the plain
reference), ``control`` (the reference computed in bfloat16, put in the
program's place) and, for training cells, the faults planted in the
reference put in the program's place: ``half_batch`` (the learner sees
half of the env batch), ``altered_action`` (env 0's actor logits are
rotated where they are produced, so its actions change: A of the A·B
lanes) and ``altered_agent`` (the same for agent 0 in every env: one
row of the rollout kernel's grid); faults run on the first
``--fault-seeds`` seeds only. A step that returns its state
unchanged reads 1 on ``change_gap``, ``moment_gap`` and ``change_diff``
by their definition and needs no run. Training rows also carry
``train.diagnostics``, which no limit holds. For the serving
cell the fault is ``altered_answer`` (lane 0 of every dispatch gets its
logits reversed in the slot forward). The last line summarises: the
largest program reading and the smallest control and fault readings of
each number. Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import cells, device  # noqa: E402


def _train_rows(cell, cfg, mix, seeds, devices, fault_seeds):
    import jax
    import jax.numpy as jnp
    from bench.lib import train, weights
    from bench.reference import common
    prog = train.Program(cfg, mix, devices)
    learn, policy = common.learn, common.policy

    def first_envs(share):
        def part(cfg_, pol, opt, batch, v_last, key, dt):
            n = v_last.shape[0] // share
            batch = jax.tree_util.tree_map(lambda x: x[:, :n], batch)
            return learn(cfg_, pol, opt, batch, v_last[:n], key, dt)
        return part

    def rotated_policy(lanes):
        def fn(cfg_, p, x, dt):
            logits, v = policy(cfg_, p, x, dt)
            if x.ndim == 3:                  # the actor, (B, A, S)
                logits = logits.at[lanes].set(
                    jnp.roll(logits[lanes], 1, axis=-1) + 1.0)
            return logits, v
        return fn

    def both(a, b):
        return {**train.readings(a, b), **train.diagnostics(a, b)}

    for seed in seeds:
        state = prog.start(seed)
        keys = train.iteration_keys(seed, 0, train.CHECKED)
        state, got = train.checked_steps(prog, state, keys)
        del state
        aip = prog.aip
        ref = train.reference_steps(cfg, mix, seed, got["p0"], aip,
                                    jnp.float32)
        yield seed, "program", both(got, ref)
        ctl = train.reference_steps(cfg, mix, seed, got["p0"], aip,
                                    jnp.bfloat16)
        yield seed, "control", both(ctl, ref)
        if seed not in fault_seeds:
            continue
        faults = [("half_batch", "learn", first_envs(2)),
                  ("altered_action", "policy", rotated_policy(0)),
                  ("altered_agent", "policy",
                   rotated_policy((slice(None), 0)))]
        for name, attr, fn in faults:
            setattr(common, attr, fn)
            try:
                bad = train.reference_steps(cfg, mix, seed, got["p0"], aip,
                                            jnp.float32)
            finally:
                common.learn, common.policy = learn, policy
            yield seed, name, both(bad, ref)


def _serve_rows(cell, cfg, mix, seeds, seconds):
    import jax.numpy as jnp
    import numpy as np
    from bench.lib import serve
    from repro.kernels import ops
    forward = ops.serve_forward

    def altered(frames, mask, pol_w, **kw):
        logits, v = forward(frames, mask, pol_w, **kw)
        return logits.at[0].set(logits[0, ::-1]), v

    for seed in seeds:
        for name in ("program", "altered_answer"):
            ops.serve_forward = forward if name == "program" else altered
            try:
                srv = serve.Server(cfg, mix, seed)
                srv.replay(serve.trace_for(cfg, mix, seed + 1, mix["warm_s"]))
                tr = serve.trace_for(cfg, mix, seed, seconds)
                res = srv.replay(tr)
            finally:
                ops.serve_forward = forward
            rng = np.random.default_rng([seed, 4])
            idx = np.sort(rng.choice(len(tr["arrival"]),
                                     size=min(len(tr["arrival"]),
                                              mix["check_sample"]),
                                     replace=False))
            acts, logits = srv.served_outputs(res["where"], idx)
            ref = serve.reference_logits(cfg, srv.params, tr["frame"][idx],
                                         jnp.float32)
            yield seed, name, serve.readings(acts, logits, ref)
            if name == "program":
                low = serve.reference_logits(cfg, srv.params,
                                             tr["frame"][idx], jnp.bfloat16)
                yield seed, "control", serve.readings(low.argmax(-1), low,
                                                      ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = cells.cell(args.workload)
    cfg, mix = cells.config(cell["config"]), cells.mix(cell["traffic"])
    try:
        devices = device.require_chips(cell["chips"])
    except device.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    device.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = (_train_rows(cell, cfg, mix, seeds, devices,
                        seeds[:args.fault_seeds])
            if mix["kind"] == "train"
            else _serve_rows(cell, cfg, mix, seeds, args.seconds))
    summary = {}
    for seed, source, r in rows:
        print(json.dumps({"seed": seed, "source": source, **r}), flush=True)
        for k, v in r.items():
            s = summary.setdefault(source, {})
            s[k] = (max if source == "program" else min)(s.get(k, v), v)
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
