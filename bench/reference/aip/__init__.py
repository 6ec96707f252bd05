"""The AIP backbones, one module per ``aip.kind`` of a configuration.

Each module holds everything the benchmark knows about its backbone:

- ``init(cfg, key)``: the per-agent weights, (A, ...) stacked in the
  engine's layout, head bias included;
- ``zero(cfg, B, A)``: the AIP state a fresh lane starts from;
- ``step(cfg, w, s, d, dt)``: one tick -> (new state, influence logits);
- ``flops(cfg)``, ``state_words(cfg)``, ``weight_words(cfg)``: one cell's
  matmul operations, and the state and weights in 4-byte words.
"""
from __future__ import annotations

from bench.lib.cells import find


def module(cfg: dict):
    return find(__name__, cfg["aip"]["kind"])
