"""The feed-forward AIP: each agent's last ``stack`` d-sets, two ReLU
layers of ``hidden``, and a head of ``n_influence`` logits."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import dense, per_agent


def init(cfg, key):
    a, d, M = cfg["aip"], cfg["dset_dim"], cfg["n_influence"]
    K, lead = a["hidden"], (cfg["n_agents"],)
    k1, k2, k3 = jax.random.split(key, 3)
    head = dense(k3, K, M, lead=lead)
    head["b"] = head["b"] + a["head_bias"]
    return {"l1": dense(k1, a["stack"] * d, K, lead=lead),
            "l2": dense(k2, K, K, lead=lead), "head": head}


def zero(cfg, B, A):
    return jnp.zeros((B, A, cfg["aip"]["stack"], cfg["dset_dim"]),
                     jnp.float32)


def step(cfg, w, s, d, dt):
    buf = jnp.concatenate([s[..., 1:, :], d[..., None, :]], axis=-2)
    x = buf.reshape(buf.shape[:2] + (-1,))
    h = jax.nn.relu(per_agent(x, w["l1"]["w"], dt) + w["l1"]["b"].astype(dt))
    h = jax.nn.relu(per_agent(h, w["l2"]["w"], dt) + w["l2"]["b"].astype(dt))
    lg = per_agent(h, w["head"]["w"], dt) + w["head"]["b"].astype(dt)
    return buf, lg.astype(jnp.float32)


def flops(cfg):
    """stack*d -> K -> K -> M."""
    a, d, M = cfg["aip"], cfg["dset_dim"], cfg["n_influence"]
    K = a["hidden"]
    return 2 * (a["stack"] * d * K + K * K + K * M)


def state_words(cfg):
    return cfg["aip"]["stack"] * cfg["dset_dim"]


def weight_words(cfg):
    a, d, M = cfg["aip"], cfg["dset_dim"], cfg["n_influence"]
    K = a["hidden"]
    return a["stack"] * d * K + K + K * K + K + K * M + M
