"""RL training driver — the paper's workflow end-to-end (its Fig. 3/5 runs).

    PYTHONPATH=src python -m repro.launch.rl_train --domain traffic \
        --simulator ials --iterations 60

Pipeline per the paper (§5.1):
  1. collect a (d_t, u_t) dataset from the GS under a random policy (Alg. 1)
  2. train the AIP offline (Eq. 3)
  3. train PPO on the chosen simulator: gs | ials | untrained-ials | f-ials
  4. periodically evaluate on the GS (the deployment environment)

Multi-agent (Distributed IALS, ``--n-agents A``): one GS rollout collects
every agent's (d_t, u_t) pairs, A per-agent AIPs train in a single batched
pass (vmap of the training loop), PPO is parameter-shared across agents with
the agent axis as extra batch dimension, and evaluation reports per-agent GS
rewards. ``--n-agents 25`` on traffic = every intersection of the 5x5 grid;
``--n-agents 36`` on warehouse = every robot region. ``--devices N`` places
the rollout batch on the ``data`` axis of an N-device mesh (default: one
device, no mesh).

Fault tolerance (the kill-and-resume contract, docs/ARCHITECTURE.md §7):
``--ckpt-dir`` makes the run preemption-safe — every RNG key is derived by
position (``fold_in(root, stream), it``), never by a split chain, so the
checkpoint needs only the iteration index to rewind the randomness. The
checkpoint carries the FULL RL state: policy params, optimizer state,
rollout/env state, the trained (per-agent) AIP params the simulator was
built from, and the iteration counter. A killed run re-launched with the
same command auto-resumes from the latest committed checkpoint — skipping
dataset collection and AIP training (the AIP comes back from disk) — and
replays the **bitwise identical** remaining trajectory; the same-seed
uninterrupted run is the oracle (tests/test_actor_learner.py pins this).

``--n-workers N`` (N >= 1) switches to the disaggregated actor/learner
fleet (distributed/actor_learner.py): N rollout workers stream tagged
trajectory batches into one learner with the documented
``--max-staleness`` drop policy; ``--kill-worker W:TICK`` /
``--delay-batch W:TICK:N`` schedule deterministic faults
(distributed/fault_injection.py). The default deterministic schedule keeps
the bitwise-resume claim; ``--async-fleet`` is the free-running
throughput mode (no bitwise claim).

Emits a JSON history of (iteration, wallclock, train reward, GS eval
reward) plus ``final_params_md5`` — the learning-curves benchmark reads
the history; the CI fault smoke compares the digest across kill/resume.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import time
from pathlib import Path
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.core import collect, engine, influence
from repro.distributed import actor_learner, fault_injection
from repro.distributed.fault_tolerance import TrainingGuard
from repro.envs.traffic import (TrafficConfig, make_traffic_env,
                                make_batched_local_traffic_env,
                                make_local_traffic_env,
                                make_multi_traffic_env)
from repro.envs.warehouse import (WarehouseConfig, make_warehouse_env,
                                  make_batched_local_warehouse_env,
                                  make_local_warehouse_env,
                                  make_multi_warehouse_env)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.rl import ppo

# fold_in stream tags — every key in the driver is fold_in(fold_in(root,
# TAG), position), so resume only needs the position (an int in the
# checkpoint), never a key chain
_K_SIM, _K_POLICY, _K_ROLLOUT, _K_TRAIN, _K_EVAL = 0, 1, 2, 3, 4


def grid_agents(grid: int, n_agents: int):
    """First ``n_agents`` cells of a grid x grid board, row-major."""
    cells = [(i, j) for i in range(grid) for j in range(grid)]
    if n_agents > len(cells):
        raise ValueError(f"n_agents={n_agents} > {grid}x{grid} grid")
    return jnp.asarray(cells[:n_agents], jnp.int32)


def build_domain(domain: str, vanish_after: int = 0, n_agents: int = 1):
    """-> (gs, ls, batched_ls, frame_stack); gs is multi-agent when
    n_agents > 1. ``batched_ls`` is the natively batched LS the fused IALS
    rollout engine steps; ``ls`` keeps the scalar protocol for tooling."""
    if domain == "traffic":
        cfg = TrafficConfig()
        if n_agents > 1:
            gs = make_multi_traffic_env(cfg, grid_agents(cfg.grid, n_agents))
        else:
            gs = make_traffic_env(cfg)
        return (gs, make_local_traffic_env(cfg),
                make_batched_local_traffic_env(cfg), 1)
    cfg = WarehouseConfig(vanish_after=vanish_after)
    if n_agents > 1:
        gs = make_multi_warehouse_env(cfg, grid_agents(cfg.grid, n_agents))
    else:
        gs = make_warehouse_env(cfg)
    return (gs, make_local_warehouse_env(cfg),
            make_batched_local_warehouse_env(cfg), 8)


def _make_sim(ls, params, acfg, n_agents, **kw):
    """``ls``: a BatchedLocalEnv — PPO trains on the unified fused rollout
    engine (one implementation for every backbone x agent-multiplicity
    combination; single-agent is the A=1 squeeze). ``mesh`` (in ``kw``)
    runs its kernel route on each device's own env lanes."""
    return engine.make_unified_ials(ls, params, acfg, n_agents=n_agents,
                                    **kw)


class SimBuild(NamedTuple):
    """A simulator recipe split at the checkpoint boundary: ``template()``
    is a cheap, shape-correct pytree of the simulator's trainable state
    (the restore target), ``train(key)`` produces the real state (dataset
    collection + AIP fit — the expensive part a resume skips), and
    ``make_env(sim_params)`` builds the PPO environment from either."""
    template: Callable[[], Any]
    train: Callable[[Any], Tuple[Any, dict]]
    make_env: Callable[[Any], Any]


def prepare_simulator(simulator: str, gs, ls, aip_kind: str, *,
                      collect_episodes: int, ep_len: int, aip_epochs: int,
                      fixed_marginal=None, aip_window: int = 0,
                      stateless_f_ials: bool = False,
                      mesh=None) -> SimBuild:
    """-> SimBuild. ``stateless_f_ials`` makes the f-ials simulator skip
    its (ignored) AIP forward pass entirely — see ``ials.make_ials`` for
    the state-shape-parity tradeoff. ``mesh`` is handed to the engine."""
    if simulator == "gs":
        return SimBuild(template=lambda: {},
                        train=lambda key: ({}, {}),
                        make_env=lambda p: gs)
    A = gs.spec.n_agents
    acfg = influence.AIPConfig(
        kind=aip_kind, d_in=gs.spec.dset_dim, n_out=gs.spec.n_influence,
        hidden=64, stack=8 if aip_kind == "fnn" else 1)

    def init_params(key):
        if A > 1:
            return jax.vmap(lambda k: influence.init_aip(acfg, k))(
                jax.random.split(key, A))
        return influence.init_aip(acfg, key)

    def agent_data(key, n_eps):
        data = collect.collect_dataset(gs, key, n_episodes=n_eps,
                                       ep_len=ep_len)
        if A > 1:
            data = collect.per_agent(data)      # (A, N, T, ...)
        return data

    if simulator == "untrained-ials":
        def train(key):
            k1, k2 = jax.random.split(key)
            data = agent_data(k1, 8)
            params = init_params(k2)
            if A > 1:
                xent = float(jnp.mean(jax.vmap(
                    lambda p, d, u: influence.xent_loss(p, acfg, d, u))(
                        params, data["d"], data["u"])))
            else:
                xent = float(influence.xent_loss(
                    params, acfg, data["d"], data["u"]))
            return params, {"aip_xent": xent}
        return SimBuild(
            template=lambda: init_params(jax.random.PRNGKey(0)),
            train=train,
            make_env=lambda p: _make_sim(ls, p, acfg, A, mesh=mesh))

    if simulator == "f-ials":
        M = gs.spec.n_influence
        marg_shape = (A, M) if A > 1 else (M,)

        def train(key):
            t0 = time.time()
            k1, k2 = jax.random.split(key)
            data = agent_data(k1, collect_episodes)
            if fixed_marginal is not None:
                marg = jnp.full(marg_shape, fixed_marginal)
            else:
                marg = collect.empirical_marginal(data["u"],
                                                  per_agent=A > 1)
            params = init_params(k2)
            # XE of the fixed marginal on held-out data
            p = jnp.clip(marg, 1e-6, 1 - 1e-6)
            if A > 1:
                p = p[:, None, None, :]         # broadcast over (A, N, T, M)
            xe = -(data["u"] * jnp.log(p)
                   + (1 - data["u"]) * jnp.log(1 - p))
            diag = {"aip_xent": float(xe.sum(-1).mean()),
                    "aip_train_time_s": time.time() - t0}
            return {"aip": params, "marg": marg}, diag
        return SimBuild(
            template=lambda: {"aip": init_params(jax.random.PRNGKey(0)),
                              "marg": jnp.zeros(marg_shape)},
            train=train,
            make_env=lambda p: _make_sim(ls, p["aip"], acfg, A,
                                         fixed_marginal_vec=p["marg"],
                                         stateless=stateless_f_ials,
                                         mesh=mesh))

    # trained IALS (the dataset is dead after the fit -> donate the
    # epoch buffers to the jitted training loop)
    def train(key):
        t0 = time.time()
        k1, k2 = jax.random.split(key)
        data = agent_data(k1, collect_episodes)
        diag = {}
        if A > 1:
            params, m = influence.train_aip_batched(
                acfg, data["d"], data["u"], jax.random.split(k2, A),
                epochs=aip_epochs, window=aip_window, donate=True)
            diag["aip_xent_per_agent"] = m["final_loss_per_agent"]
        else:
            params, m = influence.train_aip(acfg, data["d"], data["u"], k2,
                                            epochs=aip_epochs,
                                            window=aip_window, donate=True)
        diag["aip_xent"] = m["final_loss"]
        diag["aip_train_time_s"] = time.time() - t0
        return params, diag
    return SimBuild(template=lambda: init_params(jax.random.PRNGKey(0)),
                    train=train,
                    make_env=lambda p: _make_sim(ls, p, acfg, A, mesh=mesh))


def build_simulator(simulator: str, gs, ls, aip_kind: str, key, *,
                    collect_episodes: int, ep_len: int, aip_epochs: int,
                    fixed_marginal=None, aip_window: int = 0,
                    stateless_f_ials: bool = False):
    """-> (env for PPO, aip diagnostics dict) — the one-shot convenience
    wrapper over ``prepare_simulator`` for callers that never resume."""
    sb = prepare_simulator(
        simulator, gs, ls, aip_kind, collect_episodes=collect_episodes,
        ep_len=ep_len, aip_epochs=aip_epochs, fixed_marginal=fixed_marginal,
        aip_window=aip_window, stateless_f_ials=stateless_f_ials)
    sim_params, diag = sb.train(key)
    return sb.make_env(sim_params), diag


def params_md5(tree) -> str:
    """Digest of every leaf's raw bytes in tree order — two runs agree
    iff their params are bitwise identical (the resume oracle)."""
    h = hashlib.md5()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.ascontiguousarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()


def _parse_faults(kills, delays):
    events = []
    for s in kills or []:
        w, t = (int(x) for x in s.split(":"))
        events.append(fault_injection.KillWorker(worker_id=w, at_tick=t))
    for s in delays or []:
        w, t, n = (int(x) for x in s.split(":"))
        events.append(fault_injection.DelayBatch(worker_id=w, at_tick=t,
                                                 ticks=n))
    return events


def run_training(args):
    """The driver body, callable in-process (tests use this to compare a
    kill/resume pair against an uninterrupted run)."""
    root = jax.random.PRNGKey(args.seed)
    gs, _, ls, frame_stack = build_domain(args.domain, args.vanish_after,
                                          args.n_agents)
    aip_kind = args.aip or ("gru" if args.domain == "warehouse" else "fnn")
    # one device unless the caller asks for more: env lanes then shard
    # over a (data, 1) mesh of the first --devices local devices
    mesh = make_host_mesh(n_devices=args.devices) if args.devices > 1 \
        else None
    sb = prepare_simulator(
        args.simulator, gs, ls, aip_kind,
        collect_episodes=args.collect_episodes, ep_len=args.episode_len,
        aip_epochs=args.aip_epochs, fixed_marginal=args.fixed_marginal,
        stateless_f_ials=args.stateless_f_ials, mesh=mesh)

    pcfg = ppo.PPOConfig(obs_dim=gs.spec.obs_dim,
                         n_actions=gs.spec.n_actions,
                         frame_stack=frame_stack, n_envs=args.n_envs,
                         rollout_len=args.rollout_len,
                         episode_len=args.episode_len,
                         n_agents=args.n_agents,
                         fast_gates=not args.exact_policy_tanh)
    t_start = time.time()
    guard = (TrainingGuard(args.ckpt_dir, save_every=args.save_every)
             if args.ckpt_dir else None)
    resume_step = (ckpt.latest_step(args.ckpt_dir)
                   if args.ckpt_dir else None)

    def eval_row(row, params, it):
        ke = jax.random.fold_in(jax.random.fold_in(root, _K_EVAL), it)
        if args.n_agents > 1:
            per = ppo.evaluate(gs, pcfg, params, ke, n_episodes=8,
                               per_agent=True)
            row["gs_eval_reward_per_agent"] = [round(float(r), 4)
                                               for r in per]
            row["gs_eval_reward"] = float(per.mean())
        else:
            row["gs_eval_reward"] = ppo.evaluate(gs, pcfg, params, ke,
                                                 n_episodes=8)
        return row

    if args.n_workers > 0:
        out = _run_fleet(args, root, sb, pcfg, guard, resume_step,
                         eval_row, t_start)
    else:
        out = _run_integrated(args, root, sb, pcfg, mesh, guard,
                              resume_step, eval_row, t_start)
    if guard is not None:
        guard.uninstall()
    if args.out:
        Path(args.out).write_text(json.dumps(out, indent=1))
    return out


def _run_integrated(args, root, sb: SimBuild, pcfg, mesh, guard,
                    resume_step, eval_row, t_start):
    """Single-process trainer: the fused ``train_iteration`` loop with
    position-keyed RNG and full-state checkpoints."""
    start_it = 0
    if resume_step is not None:
        # restore first (shapes come from cheap templates), THEN rebuild
        # the engine from the restored AIP params — make_unified_ials
        # closes over them at construction
        env_t = sb.make_env(sb.template())
        policy_t = ppo.init_policy(pcfg, jax.random.PRNGKey(0))
        template = {"policy": policy_t,
                    "opt": ppo.make_optimizer(pcfg).init(policy_t),
                    "rs": ppo.init_rollout_state(env_t, pcfg,
                                                 jax.random.PRNGKey(0),
                                                 mesh=mesh),
                    "sim": sb.template(), "it": jnp.int32(0)}
        tree, step, _ = ckpt.restore(args.ckpt_dir, template, resume_step)
        sim_params, diag = tree["sim"], {"resumed_from": step}
        env = sb.make_env(sim_params)
        params, ost, rs = tree["policy"], tree["opt"], tree["rs"]
        start_it = int(tree["it"])
        _, iteration = ppo.make_train_iteration(env, pcfg, mesh=mesh)
        print(f"resumed from iteration {start_it}")
    else:
        sim_params, diag = sb.train(jax.random.fold_in(root, _K_SIM))
        env = sb.make_env(sim_params)
        params = ppo.init_policy(pcfg, jax.random.fold_in(root, _K_POLICY))
        opt, iteration = ppo.make_train_iteration(env, pcfg, mesh=mesh)
        ost = opt.init(params)
        rs = ppo.init_rollout_state(env, pcfg,
                                    jax.random.fold_in(root, _K_ROLLOUT),
                                    mesh=mesh)
    # every argument placed where train_iteration returns it (a restored
    # checkpoint lands on one device): one program for every iteration
    params, ost = ppo.replicate((params, ost), mesh)
    rs = ppo.shard_rollout(rs, mesh, n_agents=pcfg.n_agents)

    steps_per_iter = args.n_envs * args.rollout_len * max(args.n_agents, 1)
    history = []
    preempted = False
    for it in range(start_it, args.iterations):
        k = jax.random.fold_in(jax.random.fold_in(root, _K_TRAIN), it)
        params, ost, rs, m = iteration(params, ost, rs, k)
        row = {"iter": it, "wallclock_s": round(time.time() - t_start, 2),
               "train_reward": float(m["mean_reward"]),
               "env_steps": (it + 1) * steps_per_iter}
        if it % args.eval_every == 0 or it == args.iterations - 1:
            row = eval_row(row, params, it)
        history.append(row)
        print(json.dumps(row))
        if guard is not None:
            # read the flag BEFORE maybe_save: a successful forced save
            # clears it (the guard answers the signal once, not forever)
            was_preempted = guard.preempted
            saved = guard.maybe_save(
                it + 1,
                {"policy": params, "opt": ost, "rs": rs,
                 "sim": sim_params, "it": jnp.int32(it + 1)},
                metadata={"mode": "integrated", "iterations_done": it + 1})
            if was_preempted and saved:
                print("preempted: RL checkpoint flushed, exiting cleanly")
                preempted = True
                break

    return {"args": vars(args), "diag": diag, "history": history,
            "preempted": preempted, "resumed_from": start_it,
            "final_params_md5": params_md5(params),
            "total_wallclock_s": round(time.time() - t_start, 2)}


def _run_fleet(args, root, sb: SimBuild, pcfg, guard, resume_step,
               eval_row, t_start):
    """Disaggregated trainer: N workers -> bounded queue -> one learner,
    chunked at ``eval_every`` updates (chunk boundaries are quiescent —
    no in-flight batches — which is where checkpoints happen)."""
    fcfg = actor_learner.FleetConfig(
        n_workers=args.n_workers, queue_size=args.queue_size,
        max_staleness=args.max_staleness, publish_every=args.publish_every,
        deterministic=not args.async_fleet, seed=args.seed)
    events = _parse_faults(args.kill_worker, args.delay_batch)
    injector = (fault_injection.FaultInjector(
        fault_injection.FaultPlan.of(*events)) if events else None)

    diag = {}
    if resume_step is not None:
        env_t = sb.make_env(sb.template())
        trainer_t = actor_learner.ActorLearnerTrainer(env_t, pcfg, fcfg)
        state, sim_params, start_v = actor_learner.resume_fleet(
            args.ckpt_dir, trainer_t, extra_template=sb.template())
        diag["resumed_from"] = start_v
        print(f"resumed fleet at learner version {start_v}")
    else:
        sim_params, diag = sb.train(jax.random.fold_in(root, _K_SIM))
        state = None
    env = sb.make_env(sim_params)
    trainer = actor_learner.ActorLearnerTrainer(env, pcfg, fcfg,
                                                injector=injector)
    if state is None:
        state = trainer.init_state()

    stats = {"produced": 0, "updates": 0, "dropped": 0, "delayed": 0}
    history = []
    preempted = False
    v = int(state.version)
    while v < args.iterations:
        chunk = min(args.eval_every, args.iterations - v)
        should_stop = (lambda: guard.preempted) if guard is not None \
            else None
        state, info = trainer.run(state, chunk, should_stop=should_stop)
        for k in stats:
            stats[k] += info[k]
        v = int(state.version)
        for h in info["history"]:
            row = {"iter": h["version"], "worker": h["worker"],
                   "staleness": h["staleness"], "dropped": h["dropped"]}
            if not h["dropped"]:
                row["train_reward"] = h["mean_reward"]
            history.append(row)
        row = eval_row({"iter": v,
                        "wallclock_s": round(time.time() - t_start, 2)},
                       state.params, v)
        history.append(row)
        print(json.dumps(row))
        if guard is not None:
            was_preempted = guard.preempted
            saved = guard.maybe_save(
                v, {"fleet": state, "extra": sim_params},
                metadata={"mode": "fleet", **trainer.save_metadata(state)})
            if was_preempted and saved:
                print("preempted: fleet checkpoint flushed, exiting cleanly")
                preempted = True
                break
    if guard is not None and not preempted:
        guard.maybe_save(v, {"fleet": state, "extra": sim_params},
                         force=True,
                         metadata={"mode": "fleet",
                                   **trainer.save_metadata(state)})
    if injector is not None:
        stats["kills"] = injector.kills_applied
        stats["faults_exhausted"] = injector.exhausted

    return {"args": vars(args), "diag": diag, "history": history,
            "fleet": stats, "preempted": preempted,
            "final_params_md5": params_md5(state.params),
            "total_wallclock_s": round(time.time() - t_start, 2)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--domain", choices=["traffic", "warehouse"],
                    default="traffic")
    ap.add_argument("--simulator", default="ials",
                    choices=["gs", "ials", "untrained-ials", "f-ials"])
    ap.add_argument("--aip", default=None, choices=[None, "gru", "fnn"])
    ap.add_argument("--fixed-marginal", type=float, default=None)
    ap.add_argument("--stateless-f-ials", action="store_true",
                    help="f-ials only: freeze the ignored AIP recurrent "
                         "state instead of advancing it every tick")
    ap.add_argument("--exact-policy-tanh", action="store_true",
                    help="evaluate the PPO policy net with exact jnp.tanh "
                         "instead of the default rational gates "
                         "(nn/act.py)")
    ap.add_argument("--n-agents", type=int, default=1,
                    help="agents trained at once (25 = full 5x5 traffic "
                         "grid, 36 = full 6x6 warehouse floor)")
    ap.add_argument("--iterations", type=int, default=40)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--n-envs", type=int, default=16)
    ap.add_argument("--rollout-len", type=int, default=128)
    ap.add_argument("--episode-len", type=int, default=128)
    ap.add_argument("--collect-episodes", type=int, default=64)
    ap.add_argument("--aip-epochs", type=int, default=10)
    ap.add_argument("--vanish-after", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=1,
                    help="local devices to shard env lanes over (1 = a "
                         "single device, no mesh)")
    ap.add_argument("--out", default="")
    # fault tolerance / disaggregation
    ap.add_argument("--ckpt-dir", default="",
                    help="enable preemption-safe checkpointing + "
                         "auto-resume (bitwise on the deterministic paths)")
    ap.add_argument("--save-every", type=int, default=5,
                    help="checkpoint every N learner iterations "
                         "(SIGTERM always forces a flush)")
    ap.add_argument("--n-workers", type=int, default=0,
                    help="rollout workers for the disaggregated "
                         "actor/learner fleet (0 = integrated trainer)")
    ap.add_argument("--max-staleness", type=int, default=4,
                    help="drop trajectory batches staler than this many "
                         "policy versions")
    ap.add_argument("--publish-every", type=int, default=1,
                    help="learner updates between parameter publications")
    ap.add_argument("--queue-size", type=int, default=8)
    ap.add_argument("--async-fleet", action="store_true",
                    help="free-running worker threads (throughput mode; "
                         "no bitwise-resume claim)")
    ap.add_argument("--kill-worker", action="append", metavar="W:TICK",
                    help="deterministically kill+restart worker W before "
                         "its produce at fleet tick TICK (repeatable)")
    ap.add_argument("--delay-batch", action="append", metavar="W:TICK:N",
                    help="hold the batch worker W produces at TICK for N "
                         "ticks (drives it past --max-staleness)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    return run_training(args)


if __name__ == "__main__":
    main()
