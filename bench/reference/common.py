"""Plain reference of one PPO iteration on a Distributed IALS: the policy
acts in A local simulators per env copy, each with its own influence
predictor (AIP), for T ticks; then GAE and clipped-PPO minibatch epochs
with Adam, by the learner of the configuration's ``policy.kind``
(``bench/reference/policy/<kind>.py``). Written from the papers'
description (Suau et al. 2022; Schulman et al. 2017) in straightforward
``jax.numpy``: one scan over ticks in (B, A, ...) layout, a sequential
GAE, and no kernels, caches or program code. It imports nothing of the
program.

``dt`` is the compute dtype: float32 (at JAX's default matmul precision,
the precision the configurations state) for the reference, bfloat16 for
the control.

The randomness follows the trainer's documented key schedule, so the
reference meets the program on the same draws: an iteration key splits
into (rollout, update); the rollout key into T tick keys, each into
(action, env, reset); actions are Gumbel-argmax; env noise splits into
(AIP bits, LS noise); reset states are drawn per tick and merged where an
episode ends; the update key splits into one key an epoch (the pooled
learner, ``pooled.py``, permutes the flattened (T, B, A) samples by it).
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from bench.reference import aip as aips
from bench.reference import policy as policies

_CLAMP = 4.97178686


def tanh_r(x):
    """The configurations' rational gate: Lambert's continued fraction of
    tanh, degree 7/6, clamped where it reaches 1."""
    x = jnp.clip(x, -_CLAMP, _CLAMP)
    x2 = x * x
    return (x * (135135.0 + x2 * (17325.0 + x2 * (378.0 + x2)))
            / (135135.0 + x2 * (62370.0 + x2 * (3150.0 + x2 * 28.0))))


def sigmoid_r(x):
    return 0.5 * (tanh_r(0.5 * x) + 1.0)


def uniform(bits):
    """uint32 bits -> [0, 1) from the top 24 bits."""
    return (bits >> 8).astype(jnp.int32).astype(jnp.float32) / (1 << 24)


def domain(cfg):
    return importlib.import_module(f"bench.reference.{cfg['domain']}")


def dense(key, d_in, d_out, scale=None, lead=()):
    """A dense layer's weights: fan-in truncated normal, zero bias, with
    ``lead`` axes in front (one layer per agent)."""
    scale = d_in ** -0.5 if scale is None else scale
    return {"w": jax.random.truncated_normal(
                key, -2.0, 2.0, lead + (d_in, d_out)) * scale,
            "b": jnp.zeros(lead + (d_out,), jnp.float32)}


def per_agent(x, w, dt):
    """(B, A, i) x (A, i, j) -> (B, A, j): each agent its own weights."""
    return jnp.einsum("bai,aij->baj", x.astype(dt), w.astype(dt))


def policy(cfg, p, x, dt):
    """The configuration's policy network -> (logits, value)."""
    return policies.module(cfg).forward(p, x, dt)


def aip_step(cfg, w, s, d, dt):
    """One AIP tick per lane: -> (new state, influence logits (B, A, M))."""
    return aips.module(cfg).step(cfg, w, s, d, dt)


def aip_zero(cfg, B, A):
    return aips.module(cfg).zero(cfg, B, A)


def _split_lanes(tree, B, A):
    return jax.tree_util.tree_map(
        lambda l: l.reshape((B, A) + l.shape[1:]), tree)


def _fresh_frames(cfg, obs):
    k = cfg["policy"]["frame_stack"]
    f = jnp.zeros(obs.shape[:-1] + (k, obs.shape[-1]), jnp.float32)
    return f.at[..., -1, :].set(obs)


def initial_state(cfg, key, B):
    """The rollout state an iteration starts from: fresh LS states, zero
    AIP state, the frame stack holding the first observation."""
    dom, A = domain(cfg), cfg["n_agents"]
    ls = _split_lanes(dom.reset(key, B * A), B, A)
    return {"ls": ls, "aip": aip_zero(cfg, B, A),
            "frames": _fresh_frames(cfg, dom.observe(ls)),
            "t": jnp.zeros((B,), jnp.int32)}


def rollout(cfg, aip_w, pol, state, key, dt):
    """T acting ticks -> (state, batch (T, B, A, ...), v_last)."""
    dom, A = domain(cfg), cfg["n_agents"]
    T, ep = cfg["ppo"]["rollout_len"], cfg["ppo"]["episode_len"]
    B = state["t"].shape[0]
    n_act, M = cfg["n_actions"], cfg["n_influence"]
    keys = jax.random.split(key, T)
    k3 = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
    ka, ks, kr = k3[:, 0], k3[:, 1], k3[:, 2]
    gum = jax.vmap(lambda k: jax.random.gumbel(k, (B, A, n_act)))(ka)

    def env_noise(k):
        k_u, k_env = jax.random.split(k)
        nz = dom.noise(k_env, B * A)
        return (jax.random.bits(k_u, (B, A, M), jnp.uint32),
                None if nz is None else _split_lanes(nz, B, A))

    bits, nz = jax.vmap(env_noise)(ks)
    resets = jax.vmap(lambda k: _split_lanes(dom.reset(k, B * A), B, A))(kr)
    ticks = state["t"][None, :] + 1 + jnp.arange(T)[:, None]
    done = (ticks % ep) == 0                                   # (T, B)

    def body(st, xs):
        g, b, n, rs, dn = xs
        x = st["frames"].reshape((B, A, -1))
        logits, v = policy(cfg, pol, x, dt)
        a = jnp.argmax(logits + g, axis=-1)
        d = dom.dset(st["ls"], a)
        s2, lg = aip_step(cfg, aip_w, st["aip"], d, dt)
        u = (uniform(b) < sigmoid_r(lg.astype(dt)).astype(jnp.float32)
             ).astype(jnp.float32)
        ls2, r = dom.tick(st["ls"], a, u, n)
        frames = jnp.concatenate(
            [st["frames"][..., 1:, :], dom.observe(ls2)[..., None, :]],
            axis=-2)
        m = dn[:, None]
        ls2 = jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                m.reshape(m.shape + (1,) * (new.ndim - 2)), old, new),
            ls2, rs)
        s2 = jnp.where(m.reshape(m.shape + (1,) * (s2.ndim - 2)), 0.0, s2)
        frames = jnp.where(m[..., None, None],
                           _fresh_frames(cfg, dom.observe(ls2)), frames)
        out = {"x": x, "a": a, "logits": logits, "v": v, "r": r,
               "done": jnp.broadcast_to(m, r.shape).astype(jnp.float32)}
        return {"ls": ls2, "aip": s2, "frames": frames, "t": st["t"]}, out

    st, batch = jax.lax.scan(body, state, (gum, bits, nz, resets, done))
    st["t"] = (state["t"] + T) % ep
    _, v_last = policy(cfg, pol, st["frames"].reshape((B, A, -1)), dt)
    lsm = jax.nn.log_softmax(batch["logits"])
    batch["logp"] = jnp.take_along_axis(lsm, batch["a"][..., None], -1)[..., 0]
    return st, batch, v_last


def gae(batch, v_last, gamma, lam):
    """Sequential generalised advantage estimation, last tick first."""
    def back(adv, xs):
        r, v, v_next, done = xs
        nonterm = 1.0 - done
        delta = r + gamma * v_next * nonterm - v
        adv = delta + gamma * lam * nonterm * adv
        return adv, adv

    v = batch["v"]
    v_next = jnp.concatenate([v[1:], v_last[None]], axis=0)
    _, adv = jax.lax.scan(back, jnp.zeros_like(v_last),
                          (batch["r"], v, v_next, batch["done"]),
                          reverse=True)
    return adv, adv + v


def adam_init(pol):
    z = jax.tree_util.tree_map(jnp.zeros_like, pol)
    return {"step": jnp.zeros((), jnp.int32), "mu": z, "nu": z}


def learn(cfg, pol, opt, batch, v_last, key, dt):
    """The learner of the configuration's ``policy.kind``: GAE and PPO
    epochs over the rollout's (T, B, A) batch -> (pol, opt, mean loss).
    ``opt`` is ``adam_init``'s state, ``mu`` Adam's first moment."""
    return policies.module(cfg).learn(cfg, pol, opt, batch, v_last, key, dt)


def make_iteration(cfg, dt):
    """-> jitted (aip_w, pol, opt, state, key) -> (pol, opt, state,
    {"loss", "mean_reward", "mean_value"}): one reference PPO iteration."""
    def it(aip_w, pol, opt, state, key):
        k_roll, k_upd = jax.random.split(key)
        state, batch, v_last = rollout(cfg, aip_w, pol, state, k_roll, dt)
        pol, opt, loss = learn(cfg, pol, opt, batch, v_last, k_upd, dt)
        return pol, opt, state, {"loss": loss,
                                 "mean_reward": batch["r"].mean(),
                                 "mean_value": batch["v"].mean()}
    return jax.jit(it)
