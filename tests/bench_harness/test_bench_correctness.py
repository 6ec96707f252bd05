"""``correct`` at a size a test run holds, on the CPU: the sound program
passes, the bf16 control fails, and each fault planted under the timed
path fails. The chip check is skipped; the rest of a run is the
benchmark's own runner (``bench/run.py``'s ``RUNNERS``)."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.lib import cells, serve, train, verdict, weights
from bench.lib.clock import CompileClock

SEED = 2**31 + 77          # larger than 32 signed bits hold
TRAIN_CELLS = ("traffic25_fnn.train_b256", "warehouse36_gru.train_b256")
SERVE_CELL = "traffic25_fnn.serve_r80"


class Args:
    def __init__(self, seed=SEED, seconds=0.5):
        self.seed, self.seconds, self.trace = seed, seconds, 0


def tiny(cell_name):
    """The cell's configuration and mix cut to a test's size: 4 agents,
    8 envs each, 16 ticks; serving at 2,000 requests/s."""
    cell = cells.cell(cell_name)
    cfg = copy.deepcopy(cells.config(cell["config"]))
    mix = copy.deepcopy(cells.mix(cell["traffic"]))
    if mix["kind"] == "train":
        cfg["n_agents"], mix["n_envs"], mix["max_iterations"] = 4, 8, 8
        cfg["ppo"]["rollout_len"] = cfg["ppo"]["episode_len"] = 16
    else:
        mix["rate_rps"], mix["warm_s"] = 2000.0, 0.1
    return cell, cfg, mix


def drive(cell_name):
    cell, cfg, mix = tiny(cell_name)
    out = run.RUNNERS[mix["kind"]](cell, cfg, mix, Args(), jax.devices(),
                                   CompileClock())
    ok, checks = verdict.judge(out["checks"], cells.limits(cell_name),
                               out["failed"])
    return ok, checks, out


@pytest.mark.parametrize("cell_name", TRAIN_CELLS + (SERVE_CELL,))
def test_sound_program_is_correct(cell_name):
    ok, checks, out = drive(cell_name)
    assert ok, checks
    assert out["attempted"] > 0 and out["window_compiles"] == 0


@pytest.mark.parametrize("cell_name", TRAIN_CELLS)
def test_train_control_fails(cell_name):
    _, cfg, mix = tiny(cell_name)
    w = weights.make(cfg, SEED)
    p0 = train.host(w["policy"])
    ref = train.reference_steps(cfg, mix, SEED, p0, w["aip"], jnp.float32)
    ctl = train.reference_steps(cfg, mix, SEED, p0, w["aip"], jnp.bfloat16)
    ok, checks = verdict.judge(train.readings(ctl, ref),
                               cells.limits(cell_name))
    assert not ok, checks


def test_serve_control_fails():
    _, cfg, mix = tiny(SERVE_CELL)
    params = weights.make(cfg, SEED)["policy"]
    frames = serve.trace_for(cfg, mix, SEED, 0.5)["frame"][:2048]
    ref = serve.reference_logits(cfg, params, frames, jnp.float32)
    low = serve.reference_logits(cfg, params, frames, jnp.bfloat16)
    ok, checks = verdict.judge(
        serve.readings(low.argmax(-1), low, ref), cells.limits(SERVE_CELL))
    assert not ok, checks


def _stale_state(monkeypatch):
    """A step that returns its params and optimizer state unchanged."""
    from repro.rl import ppo
    make = ppo.make_train_iteration

    def broken(env, cfg, mesh=None):
        opt, it = make(env, cfg, mesh=mesh)

        def stale(params, ost, rs, key):
            keep = jax.tree_util.tree_map(jnp.copy, (params, ost))
            _, _, rs, m = it(params, ost, rs, key)
            return keep[0], keep[1], rs, m
        return opt, stale
    monkeypatch.setattr(ppo, "make_train_iteration", broken)


def _first_envs(monkeypatch, share):
    """The learner sees only the first ``1/share`` of the env batch."""
    from repro.rl import ppo
    learner = ppo.learner_update_fn

    def broken(cfg, opt):
        upd = learner(cfg, opt)

        def part(params, ost, batch, v_last, key):
            n = v_last.shape[0] // share
            batch = jax.tree_util.tree_map(lambda x: x[:, :n], batch)
            return upd(params, ost, batch, v_last[:n], key)
        return part
    monkeypatch.setattr(ppo, "learner_update_fn", broken)


def _half_batch(monkeypatch):
    """The learner sees half of the env batch, the mean over the rest."""
    _first_envs(monkeypatch, 2)


def _altered_action(monkeypatch):
    """Env 0's sampled action is altered where it is produced."""
    from repro.rl import ppo
    sample = ppo.gumbel_argmax

    def broken(logits, g):
        a = sample(logits, g)
        return a.at[0].set((a[0] + 1) % logits.shape[-1])
    monkeypatch.setattr(ppo, "gumbel_argmax", broken)


def _altered_agent(monkeypatch):
    """Agent 0's sampled action is altered in every env: one row of the
    rollout kernel's (agent, env block, tick) grid."""
    from repro.rl import ppo
    sample = ppo.gumbel_argmax

    def broken(logits, g):
        a = sample(logits, g)
        return a.at[:, 0].set((a[:, 0] + 1) % logits.shape[-1])
    monkeypatch.setattr(ppo, "gumbel_argmax", broken)


@pytest.mark.parametrize("fault", [_stale_state, _half_batch,
                                   _altered_action, _altered_agent])
@pytest.mark.parametrize("cell_name", TRAIN_CELLS)
def test_train_fault_fails(cell_name, fault, monkeypatch):
    fault(monkeypatch)
    ok, checks, _ = drive(cell_name)
    assert not ok, checks


def test_serve_altered_answer_fails(monkeypatch):
    """Lane 0 of every dispatch gets its logits swapped in the forward."""
    from repro.kernels import ops
    forward = ops.serve_forward

    def broken(frames, mask, pol_w, **kw):
        logits, v = forward(frames, mask, pol_w, **kw)
        return logits.at[0].set(logits[0, ::-1]), v
    monkeypatch.setattr(ops, "serve_forward", broken)
    ok, checks, _ = drive(SERVE_CELL)
    assert not ok, checks

