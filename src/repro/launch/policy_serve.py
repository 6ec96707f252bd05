"""Serve a trained policy against synthetic open-loop traffic.

    PYTHONPATH=src python -m repro.launch.policy_serve --domain traffic \
        --regions 256 --rps 20000 --duration-s 2 --slot 128
    PYTHONPATH=src python -m repro.launch.policy_serve --domain traffic \
        --bimodal --buckets 16,64,256          # multi-slot bucketed server
    PYTHONPATH=src python -m repro.launch.policy_serve --domain traffic \
        --bimodal --calibrate 3 --n-policies 4 # calibrated + cross-policy
    PYTHONPATH=src python -m repro.launch.policy_serve --domain warehouse \
        --ckpt-dir ckpts/wh --slot 64 --out serve.json

The deployment half of the training story: thousands of heterogeneous
agent regions (ragged grid sizes, staggered episode phases —
``serving/request.py``'s trace model; ``--bimodal`` switches the burst
sizes to the heavy-tailed bimodal mix) stream action requests at a
fixed offered load; ``serving/scheduler.py`` packs them into slots
earliest-deadline-first — one fixed shape (``--slot``), an explicit
bucket set (``--buckets 16,64,256``), or a set calibrated offline from
the trace itself (``--calibrate K``) — and
``serving/server.py::PolicyServer`` drives each slot through a table of
jitted masked policy forwards (``kernels/ops.py::serve_forward``; with
``--n-policies N`` a cross-policy family batched per lane through
``kernels/ops.py::serve_forward_multi``). The replay reports p50/p99
request latency (arrival -> slot completion, wall clock, queueing
included), sustained QPS, and the padded-lane waste observability
(``ServeStats``: padded_lane_frac + per-shape dispatch/occupancy
counters) — the serving contract and measurement method are
docs/ARCHITECTURE.md §8.

``--ckpt-dir`` restores the policy from an ``rl_train`` checkpoint via
``checkpoint/ckpt.py::restore_subtree`` — only the ``['policy']``
leaves' bytes are read; the optimizer/rollout/simulator payload of the
training checkpoint never touches the inference process. With
``--n-policies N`` the same restored tree seeds checkpoint 0 and the
remaining N-1 are fresh inits (stand-ins for per-region fine-tunes).

Overload + chaos controls (the overload contract, ARCHITECTURE §8):
``--admission`` puts an ``serving/overload.py::AdmissionController`` in
front of the scheduler (bounded queue ``--queue-cap``, deadline
feasibility, brownout shedding) — rejections are counted in the output,
never silent. ``--faults`` replays a deterministic serving fault plan
(``distributed/fault_injection.py::parse_serve_faults``), e.g.
``slow:10:0.05,flood:0.5:0.2:4,corrupt:0:nan`` — a slow dispatch, a
traffic flood, and a hot-reload attempt whose candidate weights are
poisoned (the reload gate must reject it and keep serving on the old
weights). ``--reload-at 100,200`` schedules hot self-reload attempts at
those dispatch indices (the seam corrupt events target).
``--virtual --service-time-s S`` replays on a deterministic virtual
clock — same decisions every run (the chaos-smoke CI path). After a
fault run the driver asserts the plan is exhausted: a fault that never
fired is a configuration bug, not a pass.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from pathlib import Path

import jax

from repro.checkpoint import ckpt
from repro.distributed.fault_injection import (FaultInjector,
                                               parse_serve_faults)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.rl_train import build_domain
from repro.rl import ppo
from repro.serving import (BIMODAL_SIZES, BIMODAL_WEIGHTS,
                           AdmissionController, OverloadConfig, PolicyServer,
                           TraceConfig, calibrate_buckets, synthetic_trace)


def build_server_and_trace(args):
    """-> (PolicyServer, trace, info dict) — the driver body, callable
    in-process (tests and the serve bench reuse it)."""
    gs, _, _, frame_stack = build_domain(args.domain)
    pcfg = ppo.PPOConfig(obs_dim=gs.spec.obs_dim,
                         n_actions=gs.spec.n_actions,
                         frame_stack=frame_stack)
    n_policies = getattr(args, "n_policies", 1)
    template = ppo.init_policy(pcfg, jax.random.PRNGKey(args.seed))
    info = {"domain": args.domain, "route": args.route,
            "n_policies": n_policies}
    if args.ckpt_dir:
        params, step, meta = ckpt.restore_subtree(
            args.ckpt_dir, template, "['policy']", step=args.step)
        info["restored_step"] = step
        info["ckpt_metadata"] = meta
    else:
        params = template
    if n_policies > 1:
        params = [params] + [
            ppo.init_policy(pcfg, jax.random.PRNGKey(args.seed + 1 + n))
            for n in range(n_policies - 1)]

    tcfg = TraceConfig(n_regions=args.regions, mean_rps=args.rps,
                       horizon_s=args.duration_s,
                       frame_dim=gs.spec.obs_dim * frame_stack,
                       seed=args.seed, n_policies=n_policies)
    if getattr(args, "bimodal", False):
        tcfg = dataclasses.replace(tcfg, region_sizes=BIMODAL_SIZES,
                                   region_size_weights=BIMODAL_WEIGHTS)
    trace = synthetic_trace(tcfg)
    info["requests"] = len(trace)

    if getattr(args, "calibrate", None):
        slot = calibrate_buckets(trace, max_buckets=args.calibrate,
                                 max_slot=args.slot)
        info["calibrated"] = True
    elif getattr(args, "buckets", None):
        slot = tuple(int(s) for s in args.buckets.split(","))
    else:
        slot = args.slot
    info["slot"] = list(slot) if isinstance(slot, tuple) else slot

    server = PolicyServer(params, obs_dim=pcfg.obs_dim,
                          n_actions=pcfg.n_actions,
                          frame_stack=frame_stack, slot=slot,
                          route=args.route)
    return server, trace, info


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--domain", choices=["traffic", "warehouse"],
                    default="traffic")
    ap.add_argument("--slot", type=int, default=128,
                    help="single compiled slot shape (also the max_slot "
                         "cap for --calibrate)")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated ascending slot shapes, e.g. "
                         "16,64,256 — the bucketed multi-slot server")
    ap.add_argument("--calibrate", type=int, default=None, metavar="K",
                    help="pick <= K bucket shapes offline from the "
                         "trace's burst-size distribution "
                         "(serving/scheduler.py::calibrate_buckets); "
                         "overrides --buckets/--slot")
    ap.add_argument("--n-policies", type=int, default=1,
                    help="cross-policy batching: serve N checkpoints "
                         "from one server, lane-routed by the request's "
                         "region-family index")
    ap.add_argument("--bimodal", action="store_true",
                    help="bimodal region burst sizes (the bucketed "
                         "scheduler's target workload)")
    ap.add_argument("--regions", type=int, default=256)
    ap.add_argument("--rps", type=float, default=20000.0)
    ap.add_argument("--duration-s", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--route", choices=["auto", "interpret", "xla"],
                    default="auto")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore the policy subtree from an rl_train "
                         "checkpoint (restore_subtree: no training-state "
                         "payload read)")
    ap.add_argument("--step", type=int, default=None)
    ap.add_argument("--admission", action="store_true",
                    help="admission control in front of the scheduler: "
                         "bounded queue + deadline feasibility + brownout "
                         "(serving/overload.py::AdmissionController)")
    ap.add_argument("--queue-cap", type=int, default=8192,
                    help="bounded admission queue (pending requests)")
    ap.add_argument("--faults", default=None,
                    help="deterministic serving fault plan, e.g. "
                         "'slow:10:0.05,flood:0.5:0.2:4,corrupt:0:nan' "
                         "(fault_injection.py::parse_serve_faults)")
    ap.add_argument("--reload-at", default=None,
                    help="comma-separated dispatch indices at which to "
                         "attempt a hot self-reload (the seam corrupt "
                         "faults target)")
    ap.add_argument("--virtual", action="store_true",
                    help="deterministic virtual-clock replay: every "
                         "scheduler/admission/fault decision replays "
                         "exactly (the chaos-smoke path)")
    ap.add_argument("--service-time-s", type=float, default=1e-3,
                    help="per-dispatch service time of the virtual clock")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    server, trace, info = build_server_and_trace(args)
    admission = None
    if args.admission:
        admission = AdmissionController(OverloadConfig(
            queue_cap=args.queue_cap,
            default_latency_s=args.service_time_s))
    inj = None
    if args.faults:
        inj = FaultInjector(parse_serve_faults(args.faults))
        info["fault_plan"] = args.faults
    reload_at = (tuple(int(d) for d in args.reload_at.split(","))
                 if args.reload_at else ())
    # compile every slot program before the clock starts — the first
    # dispatch of a jitted shape is a trace+compile, not a serve latency
    server.warmup()
    report = server.serve(
        trace, mode="virtual" if args.virtual else "wallclock",
        service_time_s=args.service_time_s, admission=admission,
        faults=inj, reload_at=reload_at)
    out = {**info, **report.summary(),
           "policy_version": server.policy_version,
           "reload_log": [list(e) for e in server.reload_log]}
    if inj is not None:
        inj.assert_exhausted()   # a fault that never fired is a config bug
        out["faults_applied"] = inj.applied_counts()
    print(json.dumps(out, indent=1))
    if args.out:
        Path(args.out).write_text(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()
