"""Operations and bytes from a configuration's shapes, never from the
program's HLO.

``policy_rollout`` (one Pallas call per PPO iteration) runs, for each of
its ``lanes x T`` lane-ticks, one policy forward and one AIP cell; it
reads every lane array and stream once and writes every output once.
Matmul operations count 2 per multiply-add; elementwise work (gates, LS
lane algebra) is not counted, so the roofline share is a matmul-and-HBM
share.
"""
from __future__ import annotations

from bench.reference import aip, policy

WORD = 4          # every kernel operand is f32, int32 or uint32


def policy_flops(cfg: dict) -> int:
    """One policy forward, by its ``policy.kind`` module."""
    return policy.module(cfg).flops(cfg)


def aip_flops(cfg: dict) -> int:
    """One AIP cell, by its ``aip.kind`` module."""
    return aip.module(cfg).flops(cfg)


def model_flops_per_sample(cfg: dict) -> int:
    """Model operations per PPO sample: the rollout's policy forward and
    AIP cell, then ``epochs`` passes of forward + backward (3 forwards)
    of the policy in the learner."""
    return (policy_flops(cfg) + aip_flops(cfg)
            + cfg["ppo"]["epochs"] * 3 * policy_flops(cfg))


def _aip_state_words(cfg: dict) -> int:
    return aip.module(cfg).state_words(cfg)


def aip_weight_words(cfg: dict) -> int:
    return aip.module(cfg).weight_words(cfg)


def policy_weight_words(cfg: dict) -> int:
    """Every parameter the policy has: with one network per agent, all
    A copies."""
    return policy.module(cfg).weight_words(cfg)


def rollout_kernel_cost(cfg: dict, lanes: int, T: int,
                        agents: int) -> tuple:
    """-> (operations, bytes) of one ``policy_rollout`` call over
    ``lanes`` env lanes (of ``agents`` agents) and ``T`` ticks."""
    S = cfg["obs_dim"] * cfg["policy"]["frame_stack"]
    n_act, M = cfg["n_actions"], cfg["n_influence"]
    ls, nz = cfg["ls_state_words"], cfg["ls_noise_words"]
    flops = lanes * T * (policy_flops(cfg) + aip_flops(cfg))
    # lane arrays in and out: LS state, AIP state, policy frame stack
    lane_words = 2 * (ls + _aip_state_words(cfg) + S)
    # per tick in: gumbel, AIP bits, done flag, LS noise, reset LS state;
    # out: x, action, logits, value, reward
    tick_words = (n_act + M + 1 + nz + ls) + (S + 1 + n_act + 1 + 1)
    weights = agents * aip_weight_words(cfg) + policy_weight_words(cfg)
    nbytes = WORD * (lanes * lane_words + lanes * T * tick_words + weights)
    return flops, nbytes
