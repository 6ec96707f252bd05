"""The policy networks, one module per ``policy.kind`` of a configuration.

Each module holds everything the benchmark knows about its network:

- ``init(cfg, key)``: the parameters in the program's layout;
- ``forward(p, x, dt)`` -> (logits, value);
- ``learn(cfg, pol, opt, batch, v_last, key, dt)`` -> (pol, opt, mean
  loss): the learner, over the rollout's (T, B, A) batch, from and to
  ``common.adam_init``'s state (``pooled.learn``, where one network is
  shared by every agent);
- ``flops(cfg)``: one forward's matmul operations, for one agent's
  sample;
- ``weight_words(cfg)``: every parameter ``init`` makes, in 4-byte
  words: with one network per agent, all A copies.

Every configuration states its kind; the program's side of it is
``bench/lib/policies/<kind>.py``.
"""
from __future__ import annotations

from bench.lib.cells import find


def kind(cfg: dict) -> str:
    return cfg["policy"]["kind"]


def module(cfg: dict):
    return find(__name__, kind(cfg))
