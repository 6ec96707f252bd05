"""The program's side of each policy network, one module per
``policy.kind`` of a configuration: what ``train.Program`` and
``serve.Server`` build for that network, so that neither names a kind.

- ``train_config(cfg, spec, n_envs)``: the trainer's configuration
  (``ppo.PPOConfig``) at the configuration's widths, for the simulators'
  ``spec`` and ``n_envs`` env copies per agent;
- ``program_init(pcfg)``: the program's initialiser, key -> parameters,
  which ``weights.check_policy`` holds against the reference's ``init``;
- ``server(cfg, mix, params)``: the ``PolicyServer`` that serves
  ``params`` with the mix's slot, its parameters checked the same way.

The reference's side is ``bench/reference/policy/<kind>.py``.
"""
from __future__ import annotations

from bench.lib.cells import find


def module(cfg: dict):
    return find(__name__, cfg["policy"]["kind"])
