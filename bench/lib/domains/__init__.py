"""The program's simulators of each ``domain`` of a configuration, one
module per domain, each with ``build(cfg) -> (gs, batched ls)``: built as
``rl_train.build_domain`` builds them, at the configuration's ``grid``
and agent count. The batched local simulator is what the rollout engine
steps; the global simulator states the agent count and the widths.
Unlike ``build_domain``, which builds a single-agent simulator for one
agent, these build only multi-agent ones (``train.Program`` refuses one
agent)."""
from __future__ import annotations

from bench.lib.cells import find


def module(cfg: dict):
    return find(__name__, cfg["domain"])
