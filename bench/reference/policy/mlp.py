"""The shared actor-critic MLP: the agent's frame stack, two rational-tanh
layers of ``hidden``, then a head of ``n_actions`` logits and a value.
One network for every agent, learnt by the pooled learner."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import pooled
from bench.reference.common import dense, tanh_r

learn = pooled.learn


def init(cfg, key):
    p = cfg["policy"]
    S, H = cfg["obs_dim"] * p["frame_stack"], p["hidden"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"l1": dense(k1, S, H), "l2": dense(k2, H, H),
            "pi": dense(k3, H, cfg["n_actions"], scale=0.01),
            "v": dense(k4, H, 1, scale=0.1)}


def forward(p, x, dt):
    c = lambda a: a.astype(dt)
    h = tanh_r(jnp.dot(x.astype(dt), c(p["l1"]["w"])) + c(p["l1"]["b"]))
    h = tanh_r(jnp.dot(h, c(p["l2"]["w"])) + c(p["l2"]["b"]))
    logits = jnp.dot(h, c(p["pi"]["w"])) + c(p["pi"]["b"])
    v = jnp.dot(h, c(p["v"]["w"]))[..., 0] + c(p["v"]["b"])[0]
    return logits.astype(jnp.float32), v.astype(jnp.float32)


def flops(cfg):
    """S -> H -> H -> (n_actions + 1)."""
    p = cfg["policy"]
    S = cfg["obs_dim"] * p["frame_stack"]
    H = p["hidden"]
    return 2 * (S * H + H * H + H * (cfg["n_actions"] + 1))


def weight_words(cfg):
    p = cfg["policy"]
    S, H = cfg["obs_dim"] * p["frame_stack"], p["hidden"]
    n = cfg["n_actions"] + 1
    return S * H + H + H * H + H + H * n + n
