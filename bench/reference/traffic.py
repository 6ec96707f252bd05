"""Plain reference of the traffic local simulator (Suau et al. 2022, 5.2):
an agent's four incoming lanes of ``lane_len`` cells, indexed by travel
direction (0 south, 1 north, 2 west, 3 east). Action 0 gives the NS
approaches (0, 1) green, action 1 the EW ones. Each tick a car moves one
cell when the cell ahead is free or its car moves too; the stop-line car
leaves on green. Influence bit d then injects a car at lane d's tail when
that cell is free. Reward: the share of this tick's cars that moved (1
with no car). Written from that description as a backward pass over the
cells, independent of the program's closed-form suffix-OR.

State leaves carry (B, A) in front: lanes (B, A, 4, L) bool, phase (B, A)
int8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANE_LEN = 10
P_OCCUPIED = 0.15       # a cell's chance to hold a car at episode start


def reset(key, n: int):
    """-> n fresh local states, leaves (n, ...)."""
    return {"lanes": jax.random.bernoulli(key, P_OCCUPIED,
                                          (n, 4, LANE_LEN)),
            "phase": jnp.zeros((n,), jnp.int8)}


def noise(key, n: int):
    del key, n
    return None          # deterministic given the influence bits


def observe(s):
    lead = s["phase"].shape
    return jnp.concatenate(
        [s["lanes"].reshape(lead + (-1,)).astype(jnp.float32),
         s["phase"].astype(jnp.float32)[..., None]], axis=-1)


def dset(s, a):
    del a
    lead = s["phase"].shape
    return s["lanes"].reshape(lead + (-1,)).astype(jnp.float32)


def tick(s, a, u, nz):
    """(state, actions (B, A), influence bits (B, A, 4) f32) -> (state,
    reward (B, A) f32)."""
    del nz
    occ = s["lanes"]
    ns = a == 0
    green = jnp.stack([ns, ns, ~ns, ~ns], axis=-1)            # (B, A, 4)
    moved = [None] * LANE_LEN
    moved[-1] = occ[..., -1] & green
    for c in range(LANE_LEN - 2, -1, -1):
        moved[c] = occ[..., c] & (~occ[..., c + 1] | moved[c + 1])
    moved = jnp.stack(moved, axis=-1)
    arrived = jnp.concatenate(
        [jnp.zeros_like(moved[..., :1]), moved[..., :-1]], axis=-1)
    new = (occ & ~moved) | arrived
    inj = (u > 0.5) & ~new[..., 0]
    new = new.at[..., 0].set(new[..., 0] | inj)
    n_cars = occ.sum(axis=(-2, -1))
    n_moved = moved.sum(axis=(-2, -1))
    reward = jnp.where(n_cars > 0,
                       n_moved.astype(jnp.float32)
                       / jnp.maximum(n_cars, 1).astype(jnp.float32), 1.0)
    return {"lanes": new, "phase": a.astype(jnp.int8)}, reward
