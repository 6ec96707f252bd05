"""The warehouse floor: a robot in each of the first ``n_agents``
regions, row-major."""
from __future__ import annotations


def build(cfg: dict):
    from repro.envs import warehouse
    from repro.launch.rl_train import grid_agents
    dc = warehouse.WarehouseConfig(grid=cfg["grid"])
    return (warehouse.make_multi_warehouse_env(
                dc, grid_agents(dc.grid, cfg["n_agents"])),
            warehouse.make_batched_local_warehouse_env(dc))
