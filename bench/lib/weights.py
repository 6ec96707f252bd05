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


@functools.lru_cache(maxsize=8)
def _maker(cfg_json: str):
    """The jitted maker of a configuration's weights, each network's by
    the module of its kind."""
    import json
    import jax
    from bench.reference import aip, policy
    cfg = json.loads(cfg_json)
    pol, net = policy.module(cfg), aip.module(cfg)
    return jax.jit(lambda kp, ka: {"policy": pol.init(cfg, kp),
                                   "aip": net.init(cfg, ka)})


def make(cfg: dict, seed: int) -> dict:
    """-> {"policy": policy params, "aip": (A, ...) AIP params}, f32 on
    the default device."""
    import json
    import jax
    return _maker(json.dumps(cfg, sort_keys=True))(
        stream(seed, K_POLICY), jax.random.PRNGKey(cfg["aip"]["weights_seed"]))


def check_policy(cfg: dict, program_init) -> None:
    """Stop set-up, naming ``policy.kind``, where the parameters the
    program's policy takes (``program_init(key)``, from the kind's
    ``bench/lib/policies/<kind>.py``) are not those that the kind's
    reference (``bench/reference/policy/<kind>.py``) builds. A new policy
    kind brings both sides as new files; where their parameters differ,
    one network would run against another's reference."""
    import jax
    from bench.reference import policy

    def layout(init):
        tree = jax.eval_shape(init, jax.random.PRNGKey(0))
        return [(jax.tree_util.keystr(p), s.shape, str(s.dtype))
                for p, s in jax.tree_util.tree_flatten_with_path(tree)[0]]

    want = layout(lambda k: policy.module(cfg).init(cfg, k))
    got = layout(program_init)
    if got != want:
        raise ValueError(
            f"{cfg['name']}: policy.kind {policy.kind(cfg)!r} gives the "
            f"parameters {want}, the program's policy takes {got}")
