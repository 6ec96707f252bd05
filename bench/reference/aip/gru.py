"""The GRU AIP: each agent's hidden state of ``hidden``, updated from the
d-set through the configurations' rational gates, and a head of
``n_influence`` logits."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.common import dense, per_agent, sigmoid_r, tanh_r


def init(cfg, key):
    a, d, M = cfg["aip"], cfg["dset_dim"], cfg["n_influence"]
    K, lead = a["hidden"], (cfg["n_agents"],)
    k1, k2, k3 = jax.random.split(key, 3)
    head = dense(k3, K, M, lead=lead)
    head["b"] = head["b"] + a["head_bias"]
    wx = dense(k1, d, 3 * K, lead=lead)
    wh = dense(k2, K, 3 * K, lead=lead)
    return {"gru": {"wx": wx["w"], "wh": wh["w"], "b": wx["b"]},
            "head": head}


def zero(cfg, B, A):
    return jnp.zeros((B, A, cfg["aip"]["hidden"]), jnp.float32)


def step(cfg, w, s, d, dt):
    H = s.shape[-1]
    g = w["gru"]
    gx = per_agent(d, g["wx"], dt) + g["b"].astype(dt)
    gh = per_agent(s, g["wh"], dt)
    r = sigmoid_r(gx[..., :H] + gh[..., :H])
    z = sigmoid_r(gx[..., H:2 * H] + gh[..., H:2 * H])
    n = tanh_r(gx[..., 2 * H:] + r * gh[..., 2 * H:])
    h2 = ((1.0 - z) * n + z * s.astype(dt)).astype(jnp.float32)
    lg = per_agent(h2, w["head"]["w"], dt) + w["head"]["b"].astype(dt)
    return h2, lg.astype(jnp.float32)


def flops(cfg):
    """(d + H) -> 3H, H -> M."""
    a, d, M = cfg["aip"], cfg["dset_dim"], cfg["n_influence"]
    K = a["hidden"]
    return 2 * (d * 3 * K + K * 3 * K + K * M)


def state_words(cfg):
    return cfg["aip"]["hidden"]


def weight_words(cfg):
    a, d, M = cfg["aip"], cfg["dset_dim"], cfg["n_influence"]
    K = a["hidden"]
    return d * 3 * K + K * 3 * K + 3 * K + K * M + M
