"""``ppo``'s actor-critic MLP, one network shared by every agent: trained
by ``ppo.make_train_iteration``, served by one ``PolicyServer``."""
from __future__ import annotations

from bench.lib import weights


def train_config(cfg: dict, spec, n_envs: int):
    from repro.rl import ppo
    pc = cfg["ppo"]
    return ppo.PPOConfig(
        obs_dim=spec.obs_dim, n_actions=spec.n_actions,
        frame_stack=cfg["policy"]["frame_stack"],
        hidden=cfg["policy"]["hidden"], n_envs=n_envs,
        rollout_len=pc["rollout_len"], episode_len=pc["episode_len"],
        gamma=pc["gamma"], lam=pc["lam"], clip=pc["clip"],
        entropy_coef=pc["entropy_coef"], value_coef=pc["value_coef"],
        lr=pc["lr"], epochs=pc["epochs"],
        n_minibatches=pc["n_minibatches"], n_agents=cfg["n_agents"],
        fast_gates=cfg["policy"]["fast_gates"])


def program_init(pcfg):
    from repro.rl import ppo
    return lambda k: ppo.init_policy(pcfg, k)


def server(cfg: dict, mix: dict, params):
    from repro.rl import ppo
    from repro.serving import PolicyServer
    pol = cfg["policy"]
    pcfg = ppo.PPOConfig(
        obs_dim=cfg["obs_dim"], n_actions=cfg["n_actions"],
        frame_stack=pol["frame_stack"], hidden=pol["hidden"],
        fast_gates=pol["fast_gates"])
    # the server serves checkpoints of this network
    weights.check_policy(cfg, program_init(pcfg))
    return PolicyServer(
        params, obs_dim=cfg["obs_dim"], n_actions=cfg["n_actions"],
        frame_stack=pol["frame_stack"], slot=mix["slot"],
        fast_gates=pol["fast_gates"], route="auto")
