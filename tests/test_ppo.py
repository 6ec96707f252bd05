"""PPO: GAE correctness vs hand computation; learning on a trivial task."""
import dataclasses
import functools
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.envs.api import Env, EnvSpec
from repro.rl import ppo


def test_gae_matches_manual():
    T, N = 4, 1
    batch = {
        "v": jnp.array([[1.0], [2.0], [3.0], [4.0]]),
        "r": jnp.array([[1.0], [1.0], [1.0], [1.0]]),
        "done": jnp.zeros((T, N)),
    }
    v_last = jnp.array([5.0])
    gamma, lam = 0.9, 0.8
    adv, ret = ppo.gae(batch, v_last, gamma, lam)
    # manual backward recursion
    v = np.array([1, 2, 3, 4, 5.0])
    a = np.zeros(5)
    for t in reversed(range(4)):
        delta = 1.0 + gamma * v[t + 1] - v[t]
        a[t] = delta + gamma * lam * a[t + 1]
    np.testing.assert_allclose(np.asarray(adv[:, 0]), a[:4], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ret[:, 0]), a[:4] + v[:4],
                               rtol=1e-5)


def test_gae_respects_done():
    batch = {"v": jnp.ones((3, 1)), "r": jnp.ones((3, 1)),
             "done": jnp.array([[0.0], [1.0], [0.0]])}
    adv, _ = ppo.gae(batch, jnp.array([10.0]), 0.99, 0.95)
    # t=1 terminates: its advantage ignores everything after
    assert abs(float(adv[1, 0]) - (1.0 - 1.0)) < 1e-6


class _BanditState(NamedTuple):
    t: jax.Array


def _make_bandit():
    """Action 1 pays 1.0, action 0 pays 0.0 — PPO must find it."""
    spec = EnvSpec(name="bandit", obs_dim=2, n_actions=2, n_influence=1,
                   dset_dim=1, dset_full_dim=1)

    def reset(key):
        return _BanditState(t=jnp.int32(0))

    def observe(s):
        return jnp.ones((2,))

    def step(s, a, key):
        r = a.astype(jnp.float32)
        s2 = _BanditState(t=s.t + 1)
        return s2, observe(s2), r, {}

    return Env(spec=spec, reset=reset, step=step, observe=observe)


def test_ppo_learns_bandit():
    # gamma/lam at 0.9: a bandit has no long-horizon credit assignment, and
    # with gamma 0.99 the GAE advantage of one step is swamped by ~32 steps
    # of discounted future-action reward noise (variance, not a PPO bug).
    env = _make_bandit()
    cfg = ppo.PPOConfig(obs_dim=2, n_actions=2, n_envs=8, rollout_len=32,
                        episode_len=32, hidden=32, lr=1e-2,
                        entropy_coef=0.0, gamma=0.9, lam=0.9)
    key = jax.random.PRNGKey(0)
    params = ppo.init_policy(cfg, key)
    opt, it_fn = ppo.make_train_iteration(env, cfg)
    ost = opt.init(params)
    rs = ppo.init_rollout_state(env, cfg, key)
    rewards = []
    for i in range(15):
        key, k = jax.random.split(key)
        params, ost, rs, m = it_fn(params, ost, rs, k)
        rewards.append(float(m["mean_reward"]))
    assert rewards[-1] > 0.9, rewards


def test_ppo_fast_gates_training_equivalence():
    """The rational-gate policy net (fast_gates=True, the default — the
    path test_ppo_learns_bandit already covers) is training-equivalent
    to the exact-tanh net: PPO with exact tanh reaches the same reward
    threshold on the bandit, and the two forward passes agree to the
    gates' documented accuracy on the same params."""
    env = _make_bandit()
    cfg = ppo.PPOConfig(obs_dim=2, n_actions=2, n_envs=8, rollout_len=32,
                        episode_len=32, hidden=32, lr=1e-2,
                        entropy_coef=0.0, gamma=0.9, lam=0.9,
                        fast_gates=False)
    key = jax.random.PRNGKey(0)
    params = ppo.init_policy(cfg, key)
    opt, it_fn = ppo.make_train_iteration(env, cfg)
    ost = opt.init(params)
    rs = ppo.init_rollout_state(env, cfg, key)
    rewards = []
    for i in range(15):
        key, k = jax.random.split(key)
        params, ost, rs, m = it_fn(params, ost, rs, k)
        rewards.append(float(m["mean_reward"]))
    assert rewards[-1] > 0.9, rewards

    x = jax.random.normal(jax.random.PRNGKey(1), (64, 2))
    lg_f, v_f = ppo.policy_forward(params, x, fast_gates=True)
    lg_e, v_e = ppo.policy_forward(params, x, fast_gates=False)
    assert float(jnp.abs(lg_f - lg_e).max()) < 1e-2
    assert float(jnp.abs(v_f - v_e).max()) < 1e-2


def test_frame_stack_rollout_shapes():
    env = _make_bandit()
    cfg = ppo.PPOConfig(obs_dim=2, n_actions=2, frame_stack=4, n_envs=3,
                        rollout_len=8, episode_len=5)
    key = jax.random.PRNGKey(1)
    params = ppo.init_policy(cfg, key)
    rs = ppo.init_rollout_state(env, cfg, key)
    rs, batch, v_last = ppo.rollout(env, cfg, params, rs, key)
    assert batch["x"].shape == (8, 3, 2 * 4)
    assert v_last.shape == (3,)
    # periodic reset happened (episode_len=5 < rollout_len=8)
    assert float(batch["done"].sum()) > 0


# ---------------------------------------------------------------------------
# Phase scopes of train_iteration
# ---------------------------------------------------------------------------

PHASES = ("ppo.noise", "ppo.rollout", "ppo.gae", "ppo.shuffle", "ppo.update")
_WORK = ("fusion", "dot", "gather", "sort", "custom-call")


def _phase(op_name):
    """The innermost ``ppo.*`` component of an op name, or None."""
    return next((p for p in reversed(op_name.split("/"))
                 if p.startswith("ppo.")), None)


def _compiled_ops(hlo):
    """-> [(opcode, op_name or None, fused root's (opcode, op_name))] for
    every op of the compiled module, and {computation: root op}."""
    ops, roots, comp = [], {}, None
    for line in hlo.splitlines():
        m = re.match(r"\s+(ROOT )?%?[^\s=]+ = .*?([a-z][\w-]*)\(", line)
        if m is None:
            c = re.match(r"(?:ENTRY )?%?([^\s(]+) \(", line)
            comp = c.group(1) if c else comp
            continue
        name = re.search(r'op_name="((?:[^"\\]|\\.)*)"', line)
        name = name.group(1) if name else None
        calls = re.search(r"calls=%?([\w.\-]+)", line)
        ops.append((m.group(2), name, calls.group(1) if calls else None))
        if m.group(1):
            roots[comp] = (m.group(2), name)
    return ops, roots


@pytest.fixture
def no_compile_cache():
    """The persistent cache's key leaves op names out, so an executable
    cached before a scope existed would come back without it: compile
    afresh."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@functools.lru_cache(maxsize=None)
def _iteration_hlo(engine_route):
    """The compiled HLO text of a small traffic ``train_iteration`` on
    the hoisted scan route or the engine's ``policy_rollout`` route.
    Compile with the ``no_compile_cache`` fixture active."""
    from repro.core import engine, influence
    from repro.launch.rl_train import build_domain
    A, B, T = 4, 4, 8
    gs, _, bls, stack = build_domain("traffic", 0, A)
    acfg = influence.AIPConfig(kind="fnn", d_in=gs.spec.dset_dim,
                               n_out=gs.spec.n_influence, hidden=16,
                               stack=8)
    aip = jax.vmap(lambda k: influence.init_aip(acfg, k))(
        jax.random.split(jax.random.PRNGKey(0), A))
    env = engine.make_unified_ials(bls, aip, acfg, n_agents=A,
                                   use_horizon_kernel=engine_route)
    assert (env.policy_rollout is not None) == engine_route
    cfg = ppo.PPOConfig(obs_dim=gs.spec.obs_dim,
                        n_actions=gs.spec.n_actions, frame_stack=stack,
                        hidden=16, n_envs=B, rollout_len=T, episode_len=T,
                        epochs=2, n_agents=A)
    opt, iteration = ppo.make_train_iteration(env, cfg)
    key = jax.random.PRNGKey(1)
    params = ppo.init_policy(cfg, key)
    args = (params, opt.init(params),
            ppo.init_rollout_state(env, cfg, key), key)
    return iteration.lower(*args).compile().as_text()


@pytest.mark.parametrize("engine_route", [False, True])
def test_train_iteration_ops_each_carry_one_phase(engine_route,
                                                  no_compile_cache):
    """The compiled ``train_iteration`` (the hoisted scan route, and the
    engine's whole-horizon ``policy_rollout`` route) carries all five
    phase scopes, and every fusion, dot, gather, sort and custom call
    carries exactly one phase: the innermost ``ppo.*`` component of its
    op name (a fusion's own, else its root's). The only ops with no op
    name are ones XLA made itself, which hold no program work: a
    broadcast of a constant, a rewritten reduction."""
    ops, roots = _compiled_ops(_iteration_hlo(engine_route))

    seen = {_phase(n) for _, n, _ in ops if n}
    assert set(PHASES) <= seen
    for opcode, name, calls in ops:
        if opcode not in _WORK:
            continue
        root_op, root_name = roots.get(calls, (None, None))
        name = name or root_name
        if name is None:
            assert opcode == "fusion" and root_op in (
                "broadcast", "reduce-window"), (opcode, root_op)
            continue
        assert _phase(name) in PHASES, (opcode, name)


@pytest.mark.parametrize("engine_route", [False, True])
def test_shuffle_is_one_gather_per_epoch(engine_route, no_compile_cache):
    """The shuffle gathers one packed per-sample record inside the epoch
    loop: exactly one ``gather`` of the compiled ``train_iteration`` has
    ``ppo.shuffle`` as its innermost phase, and it sits in the body of
    the update's epoch loop, not once per field."""
    ops, _ = _compiled_ops(_iteration_hlo(engine_route))
    shuffle = [n for op, n, _ in ops
               if op == "gather" and n and _phase(n) == "ppo.shuffle"]
    assert len(shuffle) == 1, shuffle
    assert "ppo.update/while/body/" in shuffle[0], shuffle[0]


def _shuffle_gathers(hlo):
    """-> [row width] of each ``gather`` whose phase is ppo.shuffle."""
    widths = []
    for line in hlo.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if (" gather(" in line and name
                and _phase(name.group(1)) == "ppo.shuffle"):
            widths.append(int(re.search(r"slice_sizes=\{1,(\d+)\}",
                                        line).group(1)))
    return widths


@pytest.mark.parametrize("obs_dim,stack,want", [
    (41, 1, [128]),         # traffic's 41-wide frames
    (37, 8, [4, 296]),      # warehouse's 296-wide frames
], ids=["narrow", "wide"])
def test_shuffle_record_width(obs_dim, stack, want, no_compile_cache):
    """Frames that fit one lane tile beside the four scalars ride in
    the record, zero-padded to 128 lanes: one gather per epoch. Wider
    frames are gathered on their own beside a 4-wide scalar record."""
    cfg = ppo.PPOConfig(obs_dim=obs_dim, n_actions=5, frame_stack=stack,
                        hidden=16, n_envs=8, rollout_len=8, episode_len=8,
                        epochs=2, n_minibatches=1)
    opt = ppo.make_optimizer(cfg)
    params = ppo.init_policy(cfg, jax.random.PRNGKey(0))
    lead = (cfg.rollout_len, cfg.n_envs)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    batch = {"x": f32(*lead, obs_dim * stack),
             "a": jax.ShapeDtypeStruct(lead, jnp.int32), "logp": f32(*lead),
             "v": f32(*lead), "r": f32(*lead), "done": f32(*lead)}
    hlo = jax.jit(ppo.learner_update_fn(cfg, opt)).lower(
        params, opt.init(params), batch, f32(cfg.n_envs),
        jax.random.PRNGKey(1)).compile().as_text()
    assert sorted(_shuffle_gathers(hlo)) == want


# ---------------------------------------------------------------------------
# The packed-record shuffle against the per-field gathers it replaced
# ---------------------------------------------------------------------------

def _five_gather_learner(cfg, opt):
    """Oracle: the learner with one permutation gather per field."""
    def learner_update(params, opt_state, batch, v_last, key):
        adv, ret = ppo.gae(batch, v_last, cfg.gamma, cfg.lam)
        total = batch["a"].size
        flat = {"x": batch["x"].reshape(total, -1),
                "a": batch["a"].reshape(total),
                "logp": batch["logp"].reshape(total),
                "adv": adv.reshape(total), "ret": ret.reshape(total)}
        n_mb = cfg.n_minibatches
        mb_size = total // n_mb

        def epoch(carry, k):
            perm = jax.random.permutation(k, total)[:n_mb * mb_size]
            shuf = jax.tree_util.tree_map(
                lambda v: v[perm].reshape((n_mb, mb_size) + v.shape[1:]),
                flat)

            def mb_step(carry, mb):
                params, opt_state = carry
                (l, _), g = jax.value_and_grad(ppo.ppo_loss, has_aux=True)(
                    params, cfg, mb)
                params, opt_state, _ = opt.update(g, opt_state, params)
                return (params, opt_state), l

            carry, ls = jax.lax.scan(mb_step, carry, shuf)
            return carry, ls.mean()

        (params, opt_state), losses = jax.lax.scan(
            epoch, (params, opt_state), jax.random.split(key, cfg.epochs))
        return params, opt_state, losses.mean()

    return learner_update


@pytest.mark.parametrize("n_agents,n_actions,n_envs,T,obs_dim", [
    (5, 2, 3, 8, 3),     # multi-agent, traffic's two actions
    (1, 5, 5, 7, 3),     # single agent; 35 samples leave 3 unused
    (4, 5, 2, 8, 40),    # frames wider than a lane tile: no packing
], ids=["agents5", "single", "wide"])
def test_packed_shuffle_matches_per_field_gathers(monkeypatch, n_agents,
                                                  n_actions, n_envs, T,
                                                  obs_dim):
    """The learner's packed-record epochs feed ``ppo_loss`` the same
    minibatches, field by field and bit for bit (``a`` still int32), as
    five per-field gathers under the same permutation, and end on the
    same params and Adam state."""
    cfg = ppo.PPOConfig(obs_dim=obs_dim, n_actions=n_actions, frame_stack=4,
                        hidden=16, n_envs=n_envs, rollout_len=T,
                        episode_len=T, epochs=3, n_minibatches=4,
                        n_agents=n_agents)
    lead = (T, n_envs) + cfg.agent_shape
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    batch = {
        "x": jax.random.normal(ks[0], lead + (cfg.obs_dim * cfg.frame_stack,)),
        "a": jax.random.randint(ks[1], lead, 0, n_actions, jnp.int32),
        "logp": -jax.random.uniform(ks[2], lead, minval=0.1, maxval=2.0),
        "v": jax.random.normal(ks[3], lead),
        "r": jax.random.normal(ks[4], lead),
        "done": (jax.random.uniform(ks[5], lead) < 0.2).astype(jnp.float32),
    }
    v_last = jax.random.normal(ks[6], lead[1:])
    params = ppo.init_policy(cfg, ks[7])
    opt = ppo.make_optimizer(cfg)
    key = jax.random.PRNGKey(4)

    seen = {"packed": [], "oracle": []}
    loss = ppo.ppo_loss

    def run(make, tag):
        def spy(params, cfg, mb):
            jax.debug.callback(lambda mb: seen[tag].append(mb), mb,
                               ordered=True)
            return loss(params, cfg, mb)
        monkeypatch.setattr(ppo, "ppo_loss", spy)
        upd = jax.jit(make(cfg, opt))
        out = upd(params, opt.init(params), batch, v_last, key)
        jax.effects_barrier()
        return out

    p1, o1, _ = run(ppo.learner_update_fn, "packed")
    p0, o0, _ = run(_five_gather_learner, "oracle")

    assert len(seen["packed"]) == len(seen["oracle"]) == (
        cfg.epochs * cfg.n_minibatches)
    for mb1, mb0 in zip(seen["packed"], seen["oracle"]):
        assert mb1.keys() == mb0.keys()
        assert mb1["a"].dtype == np.int32
        for k in mb0:
            assert mb1[k].dtype == mb0[k].dtype, k
            np.testing.assert_array_equal(mb1[k], mb0[k], err_msg=k)
    for got, want in zip(jax.tree_util.tree_leaves((p1, o1)),
                         jax.tree_util.tree_leaves((p0, o0))):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
