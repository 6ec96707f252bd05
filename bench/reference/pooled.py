"""The pooled PPO learner: one policy shared by every agent, learnt from
all agents' samples at once. GAE, then ``epochs`` passes of
``n_minibatches`` clipped-PPO Adam steps over a fresh permutation of the
flattened (T, B, A) samples each; advantages are normalised over a whole
minibatch, and Adam clips one global norm over every parameter.

A policy kind that learns this way names ``learn`` in its module
(``bench/reference/policy/<kind>.py``); one that learns otherwise, an
independent learner per agent, say, states its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import common


def ppo_loss(cfg, pol, mb, dt):
    pc = cfg["ppo"]
    logits, v = common.policy(cfg, pol, mb["x"], dt)
    lsm = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(lsm, mb["a"][:, None], -1)[:, 0]
    ratio = jnp.exp(logp - mb["logp"])
    adv = (mb["adv"] - mb["adv"].mean()) / (mb["adv"].std() + 1e-8)
    pg = -jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - pc["clip"],
                                            1 + pc["clip"]) * adv).mean()
    v_loss = jnp.square(v - mb["ret"]).mean()
    ent = -(jnp.exp(lsm) * lsm).sum(-1).mean()
    return pg + pc["value_coef"] * v_loss - pc["entropy_coef"] * ent


def adam(cfg, pol, opt, g):
    """Adam with global-norm gradient clipping, no weight decay."""
    pc = cfg["ppo"]
    leaves = jax.tree_util.tree_leaves(g)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
    g = jax.tree_util.tree_map(
        lambda x: x * jnp.minimum(1.0, pc["clip_norm"] / jnp.maximum(norm, 1e-9)), g)
    step = opt["step"] + 1
    b1, b2 = pc["adam_b1"], pc["adam_b2"]
    mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, opt["mu"], g)
    nu = jax.tree_util.tree_map(lambda n, x: b2 * n + (1 - b2) * x * x, opt["nu"], g)
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)
    pol = jax.tree_util.tree_map(
        lambda p, m, n: p - pc["lr"] * ((m / c1) / (jnp.sqrt(n / c2) + pc["adam_eps"])),
        pol, mu, nu)
    return pol, {"step": step, "mu": mu, "nu": nu}


def learn(cfg, pol, opt, batch, v_last, key, dt):
    """-> (pol, opt, mean loss) after the epochs over ``batch``."""
    pc = cfg["ppo"]
    adv, ret = common.gae(batch, v_last, pc["gamma"], pc["lam"])
    total = batch["a"].size
    flat = {"x": batch["x"].reshape(total, -1), "a": batch["a"].reshape(total),
            "logp": batch["logp"].reshape(total),
            "adv": adv.reshape(total), "ret": ret.reshape(total)}
    n_mb = pc["n_minibatches"]
    size = total // n_mb

    def epoch(carry, k):
        perm = jax.random.permutation(k, total)[:n_mb * size]
        shuf = jax.tree_util.tree_map(
            lambda v: v[perm].reshape((n_mb, size) + v.shape[1:]), flat)

        def step(carry, mb):
            pol, opt = carry
            loss, g = jax.value_and_grad(
                lambda p: ppo_loss(cfg, p, mb, dt))(pol)
            pol, opt = adam(cfg, pol, opt, g)
            return (pol, opt), loss

        carry, losses = jax.lax.scan(step, carry, shuf)
        return carry, losses

    (pol, opt), losses = jax.lax.scan(
        epoch, (pol, opt), jax.random.split(key, pc["epochs"]))
    return pol, opt, losses.mean()
