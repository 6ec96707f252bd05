"""The policy networks, one module per ``policy.kind`` of a configuration.

Each module holds everything the benchmark knows about its network:
``init(cfg, key)``, the parameters in the program's layout;
``forward(p, x, dt)`` -> (logits, value); ``flops(cfg)``, one forward's
matmul operations; ``weight_words(cfg)``. Every configuration file states
its kind; a policy block without one is the shared MLP only so that the
hand-count tests' configurations, written before kinds were named, read
as they did.
"""
from __future__ import annotations

from bench.lib.cells import find


def kind(cfg: dict) -> str:
    return cfg["policy"].get("kind", "mlp")


def module(cfg: dict):
    return find(__name__, kind(cfg))
