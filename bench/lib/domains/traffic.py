"""The traffic-light grid: an agent at each of the first ``n_agents``
intersections, row-major."""
from __future__ import annotations


def build(cfg: dict):
    from repro.envs import traffic
    from repro.launch.rl_train import grid_agents
    dc = traffic.TrafficConfig(grid=cfg["grid"])
    return (traffic.make_multi_traffic_env(
                dc, grid_agents(dc.grid, cfg["n_agents"])),
            traffic.make_batched_local_traffic_env(dc))
