"""Disaggregated actor/learner trainer + fault injection (PR 7).

Pins down the fault-tolerance contract: the deterministic fleet is a pure
function of its seed; a run killed mid-training and resumed from a
committed checkpoint replays the BITWISE identical remaining trajectory
(the uninterrupted same-seed run is the oracle — the PR's acceptance
test); stale batches are dropped, never averaged in; killed workers
restart on their own deterministic RNG streams; fleet resizes keep the
learner state; and the full integrated RL driver round-trips through the
checkpoint — including onto a forced 8-device CPU mesh (subprocess)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import ckpt
from repro.core import engine, influence
from repro.distributed import actor_learner as al
from repro.distributed import fault_injection as fi
from repro.envs.traffic import TrafficConfig, make_batched_local_traffic_env
from repro.rl import ppo

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _trees_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        jnp.array_equal(x, y) for x, y in zip(la, lb))


@pytest.fixture(scope="module")
def tiny_env():
    """A small unified-IALS engine (the fleet's intended workload)."""
    bls = make_batched_local_traffic_env(TrafficConfig())
    acfg = influence.AIPConfig(kind="fnn", d_in=bls.spec.dset_dim,
                               n_out=bls.spec.n_influence, hidden=8,
                               stack=2)
    params = influence.init_aip(acfg, jax.random.PRNGKey(0))
    return engine.make_unified_ials(bls, params, acfg)


@pytest.fixture(scope="module")
def tiny_cfg(tiny_env):
    return ppo.PPOConfig(obs_dim=tiny_env.spec.obs_dim,
                         n_actions=tiny_env.spec.n_actions,
                         frame_stack=2, n_envs=4, rollout_len=7,
                         episode_len=5, hidden=16, epochs=2)


def _fleet(deterministic=True, **kw):
    kw.setdefault("n_workers", 2)
    kw.setdefault("max_staleness", 2)
    kw.setdefault("seed", 5)
    return al.FleetConfig(deterministic=deterministic, **kw)


# ---------------------------------------------------------------------------
# determinism + the bitwise kill-and-resume acceptance test
# ---------------------------------------------------------------------------

def test_deterministic_fleet_is_seed_pure(tiny_env, tiny_cfg):
    """Two same-seed runs are bitwise identical end to end — the property
    the resume guarantee is built on."""
    outs = []
    for _ in range(2):
        tr = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet())
        state, info = tr.run(tr.init_state(), 4)
        outs.append((state, info))
    (s1, i1), (s2, i2) = outs
    assert _trees_equal(s1.params, s2.params)
    assert _trees_equal(s1.opt_state, s2.opt_state)
    assert int(s1.version) == int(s2.version) == 4
    assert [h["loss"] for h in i1["history"]] == \
           [h["loss"] for h in i2["history"]]


def test_kill_and_resume_bitwise(tiny_env, tiny_cfg, tmp_path):
    """THE acceptance test: run k updates, checkpoint, 'die', restore in
    a fresh trainer, run the remaining j — final params are bitwise equal
    to the uninterrupted k+j run's (not allclose: equal)."""
    tr = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet())
    oracle, _ = tr.run(tr.init_state(), 5)

    tr1 = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet())
    mid, _ = tr1.run(tr1.init_state(), 2)
    ckpt.save(tmp_path, int(mid.version), mid,
              metadata=tr1.save_metadata(mid))

    tr2 = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet())
    restored, extra, start = al.resume_fleet(tmp_path, tr2)
    assert extra is None and start == 2
    assert _trees_equal(restored, mid)           # exact round-trip
    final, _ = tr2.run(restored, 3)
    assert int(final.version) == 5
    assert _trees_equal(final.params, oracle.params)
    assert _trees_equal(final.opt_state, oracle.opt_state)
    for w_f, w_o in zip(final.workers, oracle.workers):
        assert int(w_f.rng_position) == int(w_o.rng_position)
        assert _trees_equal(w_f.rs, w_o.rs)


def test_resume_fleet_without_checkpoint(tmp_path, tiny_env, tiny_cfg):
    tr = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet())
    state, extra, start = al.resume_fleet(tmp_path / "none", tr)
    assert state is None and extra is None and start == 0


# ---------------------------------------------------------------------------
# staleness drop policy
# ---------------------------------------------------------------------------

def test_stale_batches_dropped_not_applied(tiny_env, tiny_cfg):
    """A batch delayed past max_staleness is counted + recorded as
    dropped, the learner still reaches the target version, and the
    history row carries the offending staleness."""
    inj = fi.FaultInjector(fi.FaultPlan.of(
        fi.DelayBatch(worker_id=0, at_tick=0, ticks=4)))
    tr = al.ActorLearnerTrainer(tiny_env, tiny_cfg,
                                _fleet(max_staleness=1), injector=inj)
    state, info = tr.run(tr.init_state(), 4)
    assert int(state.version) == 4
    assert info["delayed"] == 1
    dropped = [h for h in info["history"] if h["dropped"]]
    assert len(dropped) == 1 and dropped[0]["staleness"] > 1
    assert info["dropped"] == 1
    applied = [h for h in info["history"] if not h["dropped"]]
    assert all(h["staleness"] <= 1 for h in applied)


def test_within_staleness_batches_applied(tiny_env, tiny_cfg):
    """The same delay under a generous bound is applied, not dropped —
    the drop policy is the bound, nothing implicit."""
    inj = fi.FaultInjector(fi.FaultPlan.of(
        fi.DelayBatch(worker_id=0, at_tick=0, ticks=2)))
    tr = al.ActorLearnerTrainer(tiny_env, tiny_cfg,
                                _fleet(max_staleness=4), injector=inj)
    state, info = tr.run(tr.init_state(), 4)
    assert int(state.version) == 4
    assert info["dropped"] == 0 and info["delayed"] == 1


# ---------------------------------------------------------------------------
# worker kill / restart
# ---------------------------------------------------------------------------

def test_worker_kill_restarts_on_fresh_stream(tiny_env, tiny_cfg):
    """A killed worker loses its rollout state (restart count bumps, its
    env state re-initializes from the restart stream) but the fleet keeps
    training; the run differs from the fault-free one (the fault is
    real), deterministically (two faulted runs agree)."""
    def run_with(plan):
        inj = fi.FaultInjector(plan) if plan else None
        tr = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet(),
                                    injector=inj)
        state, info = tr.run(tr.init_state(), 4)
        return state, info, inj

    plan = fi.FaultPlan.of(fi.KillWorker(worker_id=1, at_tick=1))
    clean, _, _ = run_with(None)
    s1, i1, inj1 = run_with(plan)
    s2, _, _ = run_with(plan)
    inj1.assert_exhausted()               # the plan actually fired
    assert inj1.kills_applied == 1
    assert i1["kills"] == 1
    assert int(s1.workers[1].restarts) == 1
    assert int(s1.workers[0].restarts) == 0
    assert int(s1.version) == 4
    assert _trees_equal(s1.params, s2.params)        # faulted, replayable
    assert not _trees_equal(s1.params, clean.params)  # fault changed it


def test_fault_injector_fires_once():
    inj = fi.FaultInjector(fi.FaultPlan.of(
        fi.KillWorker(worker_id=0, at_tick=3)))
    assert not inj.should_kill(3, 1)      # wrong worker
    assert not inj.should_kill(2, 0)      # wrong tick
    with pytest.raises(AssertionError):
        inj.assert_exhausted()            # not yet fired: loud, not vacuous
    assert inj.should_kill(3, 0)
    assert not inj.should_kill(3, 0)      # consumed
    inj.assert_exhausted()
    assert inj.kills_applied == 1


# ---------------------------------------------------------------------------
# async (free-running threads) mode
# ---------------------------------------------------------------------------

def test_async_fleet_trains_and_joins(tiny_env, tiny_cfg):
    """Throughput mode liveness: reaches the target version, producers
    outlive nothing (threads joined), every applied batch respected the
    staleness bound, and worker states were collected back."""
    import threading
    before = threading.active_count()
    tr = al.ActorLearnerTrainer(tiny_env, tiny_cfg,
                                _fleet(deterministic=False,
                                       max_staleness=8))
    state, info = tr.run(tr.init_state(), 3)
    assert threading.active_count() == before
    assert int(state.version) == 3
    assert info["produced"] >= info["updates"]
    applied = [h for h in info["history"] if not h["dropped"]]
    assert all(h["staleness"] <= 8 for h in applied)
    assert all(jnp.isfinite(h["loss"]) for h in applied)
    assert sum(int(w.rng_position) for w in state.workers) \
        >= info["produced"]


# ---------------------------------------------------------------------------
# elastic fleet resize on resume
# ---------------------------------------------------------------------------

def test_fleet_resize_keeps_learner_state(tiny_env, tiny_cfg, tmp_path):
    """Resume with a different worker count: learner (params, opt state,
    version) survives bitwise; surviving workers keep their exact RNG
    stream positions; new workers start fresh at position 0."""
    tr2 = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet(n_workers=2))
    state, _ = tr2.run(tr2.init_state(), 4)
    ckpt.save(tmp_path, 4, state, metadata=tr2.save_metadata(state))

    tr3 = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet(n_workers=3))
    grown, _, start = al.resume_fleet(tmp_path, tr3)
    assert start == 4 and len(grown.workers) == 3
    assert _trees_equal(grown.params, state.params)
    assert _trees_equal(grown.opt_state, state.opt_state)
    for w_old, w_new in zip(state.workers, grown.workers[:2]):
        assert int(w_new.rng_position) == int(w_old.rng_position)
    assert int(grown.workers[2].rng_position) == 0

    tr1 = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet(n_workers=1))
    shrunk, _, _ = al.resume_fleet(tmp_path, tr1)
    assert len(shrunk.workers) == 1
    assert _trees_equal(shrunk.params, state.params)


# ---------------------------------------------------------------------------
# RL-state checkpoint round-trip (params + opt + AIP + RNG positions)
# ---------------------------------------------------------------------------

def test_rl_state_roundtrip_with_sim_params(tiny_env, tiny_cfg, tmp_path):
    """The composite tree the driver checkpoints — fleet state + the
    simulator's AIP params — round-trips bitwise, and read_metadata
    surfaces the counters without touching arrays."""
    tr = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet())
    state, _ = tr.run(tr.init_state(), 2)
    acfg = influence.AIPConfig(kind="fnn", d_in=3, n_out=2, hidden=8,
                               stack=2)
    sim = influence.init_aip(acfg, jax.random.PRNGKey(7))
    ckpt.save(tmp_path, 2, {"fleet": state, "extra": sim},
              metadata=tr.save_metadata(state))

    tr2 = al.ActorLearnerTrainer(tiny_env, tiny_cfg, _fleet())
    restored, sim_back, start = al.resume_fleet(
        tmp_path, tr2,
        extra_template=influence.init_aip(acfg, jax.random.PRNGKey(0)))
    assert start == 2
    assert _trees_equal(sim_back, sim)
    assert _trees_equal(restored, state)

    meta = ckpt.read_metadata(tmp_path)
    assert meta["n_workers"] == 2 and meta["version"] == 2
    assert meta["rng_positions"] == [int(w.rng_position)
                                     for w in state.workers]


# ---------------------------------------------------------------------------
# the full driver, killed and resumed onto a forced 8-device mesh
# ---------------------------------------------------------------------------

def test_rl_train_resume_bitwise_on_8_device_mesh(tmp_path):
    """End-to-end: the integrated rl_train driver checkpoints its full RL
    state, and a resumed run — restoring onto a forced 8-device CPU mesh
    — finishes with params bitwise equal to the uninterrupted same-seed
    run (final_params_md5 is the oracle)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import json, sys
        import jax
        from repro.launch import rl_train

        assert len(jax.devices()) == 8
        ckdir = sys.argv[1]
        base = ["--domain", "traffic", "--simulator", "ials",
                "--iterations", "3", "--eval-every", "100",
                "--n-envs", "8", "--rollout-len", "8",
                "--episode-len", "16", "--collect-episodes", "2",
                "--aip-epochs", "1", "--seed", "4", "--devices", "8"]
        full = rl_train.main(base)
        part = rl_train.main(base[:5] + ["1"] + base[6:]
                             + ["--ckpt-dir", ckdir, "--save-every", "1"])
        res = rl_train.main(base + ["--ckpt-dir", ckdir,
                                    "--save-every", "1"])
        print(json.dumps({
            "full": full["final_params_md5"],
            "resumed": res["final_params_md5"],
            "resumed_from": res["resumed_from"]}))
    """)
    out = subprocess.run([sys.executable, "-c", script,
                          str(tmp_path / "ck")],
                         capture_output=True, text=True, timeout=1200,
                         env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
                              "HOME": "/root"})
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["resumed_from"] == 1
    assert res["resumed"] == res["full"], res
