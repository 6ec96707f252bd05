"""Keys and weights from ``--seed``, made on the device in one jitted call.

The policy parameters and every input derive from the seed. The AIP
weights derive from the configuration's fixed ``aip.weights_seed``: the
AIP is the simulator's fitted model, shared by every training run on it,
and the engine compiles its weights into the program as constants, so a
seed-dependent AIP would make every run compile anew.
"""
from __future__ import annotations

import functools

# fold_in tags of the per-run key streams
K_POLICY, K_ROLLOUT, K_TRAIN, K_SAMPLE = 1, 2, 3, 4


def root_key(seed: int):
    """A key from any non-negative seed (``PRNGKey`` keeps 32 bits)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def stream(seed: int, tag: int):
    import jax
    return jax.random.fold_in(root_key(seed), tag)


def _dense(key, d_in, d_out, scale=None, lead=()):
    import jax
    import jax.numpy as jnp
    scale = d_in ** -0.5 if scale is None else scale
    return {"w": jax.random.truncated_normal(
                key, -2.0, 2.0, lead + (d_in, d_out)) * scale,
            "b": jnp.zeros(lead + (d_out,), jnp.float32)}


def _policy(cfg, key):
    import jax
    p = cfg["policy"]
    S, H = cfg["obs_dim"] * p["frame_stack"], p["hidden"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"l1": _dense(k1, S, H), "l2": _dense(k2, H, H),
            "pi": _dense(k3, H, cfg["n_actions"], scale=0.01),
            "v": _dense(k4, H, 1, scale=0.1)}


def _aip(cfg, key):
    """Per-agent AIP weights, (A, ...) stacked, in the engine's layout."""
    import jax
    a, d, M = cfg["aip"], cfg["dset_dim"], cfg["n_influence"]
    K, lead = a["hidden"], (cfg["n_agents"],)
    k1, k2, k3 = jax.random.split(key, 3)
    head = _dense(k3, K, M, lead=lead)
    head["b"] = head["b"] + a["head_bias"]
    if a["kind"] == "fnn":
        return {"l1": _dense(k1, a["stack"] * d, K, lead=lead),
                "l2": _dense(k2, K, K, lead=lead), "head": head}
    wx = _dense(k1, d, 3 * K, lead=lead)
    wh = _dense(k2, K, 3 * K, lead=lead)
    return {"gru": {"wx": wx["w"], "wh": wh["w"], "b": wx["b"]},
            "head": head}


@functools.lru_cache(maxsize=8)
def _maker(cfg_json: str):
    import json
    import jax
    cfg = json.loads(cfg_json)
    return jax.jit(lambda kp, ka: {"policy": _policy(cfg, kp),
                                   "aip": _aip(cfg, ka)})


def make(cfg: dict, seed: int) -> dict:
    """-> {"policy": policy params, "aip": (A, ...) AIP params}, f32 on
    the default device."""
    import json
    import jax
    return _maker(json.dumps(cfg, sort_keys=True))(
        stream(seed, K_POLICY), jax.random.PRNGKey(cfg["aip"]["weights_seed"]))
