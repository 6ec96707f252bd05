"""Plain reference of the warehouse local simulator (Suau et al. 2022,
5.3): one robot in a 5x5 region whose 12 edge cells hold items (top row
columns 1-3, bottom row 1-3, left column rows 1-3, right column 1-3, in
that order). Actions: 0 stay, 1 up, 2 down, 3 left, 4 right, clipped to
the region. Stepping onto an active item collects it (reward 1 each);
influence bit k removes item k (a neighbour took it). Surviving items
age by one up to ``MAX_AGE``; an empty cell spawns an item with
probability ``P_ITEM``. The d-set is the 12 item bits plus "the robot was
or is at item cell k".

State leaves carry (B, A) in front: pos (B, A, 2) int32, items (B, A, 12)
int32 (age + 1, 0 = empty).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SIDE = 5
P_ITEM = 0.02
MAX_AGE = 64
P_ITEM_AT_RESET = 0.3
ITEM_CELLS = ([(0, c) for c in (1, 2, 3)] + [(SIDE - 1, c) for c in (1, 2, 3)]
              + [(r, 0) for r in (1, 2, 3)] + [(r, SIDE - 1) for r in (1, 2, 3)])
MOVES = ((0, 0), (-1, 0), (1, 0), (0, -1), (0, 1))


def reset(key, n: int):
    k1, k2 = jax.random.split(key)
    return {"pos": jax.random.randint(k1, (n, 2), 0, SIDE),
            "items": jax.random.bernoulli(
                k2, P_ITEM_AT_RESET, (n, 12)).astype(jnp.int32)}


def noise(key, n: int):
    """Item spawns of one tick, (n, 12) bool."""
    return jax.random.bernoulli(key, P_ITEM, (n, 12))


def _at_items(pos):
    """(..., 2) -> (..., 12) bool: the item cells the robot stands on."""
    return jnp.stack([(pos[..., 0] == r) & (pos[..., 1] == c)
                      for r, c in ITEM_CELLS], axis=-1)


def _moved(pos, a):
    dr = jnp.select([a == k for k in range(5)], [m[0] for m in MOVES])
    dc = jnp.select([a == k for k in range(5)], [m[1] for m in MOVES])
    return jnp.clip(pos + jnp.stack([dr, dc], axis=-1), 0, SIDE - 1)


def observe(s):
    pos = s["pos"]
    cells = jnp.stack([(pos[..., 0] == r) & (pos[..., 1] == c)
                       for r in range(SIDE) for c in range(SIDE)], axis=-1)
    return jnp.concatenate([cells.astype(jnp.float32),
                            (s["items"] > 0).astype(jnp.float32)], axis=-1)


def dset(s, a):
    at = _at_items(s["pos"]) | _at_items(_moved(s["pos"], a))
    return jnp.concatenate([(s["items"] > 0).astype(jnp.float32),
                            at.astype(jnp.float32)], axis=-1)


def tick(s, a, u, spawn):
    pos = _moved(s["pos"], a)
    at = _at_items(pos)
    items = s["items"]
    reward = (at & (items > 0)).sum(-1).astype(jnp.float32)
    items = jnp.where(at | (u > 0.5), 0, items)
    items = jnp.where(items > 0, jnp.minimum(items + 1, MAX_AGE), 0)
    items = jnp.where((items == 0) & spawn, 1, items)
    return {"pos": pos, "items": items}, reward
