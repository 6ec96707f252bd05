"""Traffic-grid domain (paper §5.2), as a pure-JAX cellular automaton.

Global simulator (GS): a G x G grid of intersections (paper: 5x5 = 25). Each
intersection has four incoming lanes of L cells, indexed by direction of
travel d: 0=southbound, 1=northbound, 2=westbound, 3=eastbound. Cars advance
one cell per step when the next cell is free; at the stop line they cross iff
their approach has green and the downstream tail cell is free, entering the
same-direction lane of the neighbouring intersection (no turning — the
paper's influence structure only needs through traffic). Boundary lanes
inject cars with prob ``p_in`` (paper uses 0.1, App. E). Non-agent lights run
an actuated queue-comparison controller (stand-in for the Flow-optimized
controllers); the agent sets its intersection's phase each step.

Local simulator (LS): only the agent's four incoming lanes. Cars enter the
tails according to the influence sources u_t (4 bits — exactly the paper's
"car entering from each of the four incoming lanes"); crossing cars leave the
local region (open boundary).

d-set (paper: 37-bit car-location vector, lights EXCLUDED to avoid the App. B
spurious correlation): occupancy of the 4 incoming lanes = 4L bits.
``dset_full`` appends the light phase (the confounder) for the ablation.

Multi-agent (Distributed IALS): ``make_multi_traffic_env(cfg, agents)`` puts
an agent at every listed intersection — agent coordinates are ordinary traced
int arrays, so the per-agent obs/reward/u/d-set extraction is a ``vmap`` over
them and the whole grid (up to all G*G intersections) steps in one program.

``ext_influence`` widens u_t from 4 to 8 bits: the extra 4 bits mark "the
downstream tail of lane d is occupied" — the congestion feedback the 4-bit
paper version ignores. With them the LS replay of a GS rollout is *exact*
(same obs/reward sequence given the true u_t), which is what the GS<->LS
consistency tests check.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .api import (BatchedEnv, BatchedLocalEnv, Env, EnvSpec, LocalEnv,
                  squeeze_agent_env)


@dataclass(frozen=True)
class TrafficConfig:
    grid: int = 5
    lane_len: int = 10
    p_in: float = 0.1
    agent: Tuple[int, int] = (2, 2)
    min_phase: int = 2          # actuated controller hysteresis (steps)
    queue_window: int = 5       # cells from stop line counted as queue
    ext_influence: bool = False  # 8-bit u_t (+4 downstream-blocked bits)


class TrafficState(NamedTuple):
    lanes: jax.Array   # (G, G, 4, L) bool occupancy
    phase: jax.Array   # (G, G) int8: 0 = NS green (d 0,1), 1 = EW green
    timer: jax.Array   # (G, G) int32 steps since last switch


class LocalTrafficState(NamedTuple):
    lanes: jax.Array   # (4, L) bool
    phase: jax.Array   # () int8 (agent's own light, part of x_t)


def _green(phase, G):
    """(G,G) phase -> (G,G,4) approach-green mask."""
    ns = (phase == 0)
    return jnp.stack([ns, ns, ~ns, ~ns], axis=-1)


def _advance_lane(occ, can_cross):
    """One lane (..., L) synchronous advance. Returns (new_occ, moved_mask,
    crossed).

    Closed form of the backward induction
        moved[L-1] = occ[L-1] & can_cross
        moved[c]   = occ[c] & (~occ[c+1] | moved[c+1]):
    a car moves iff some cell strictly ahead is free, or everything ahead
    is occupied and the stop-line car crosses. The suffix-OR is log2(L)
    rounds of shift-and-or on the boolean lane — O(log L) fused ops
    instead of an L-stage dependent chain, which matters because this runs
    per tick in every simulator's hot loop (GS and LS alike).

    The algebra runs on int32 0/1 cells with shifts written as
    slice-and-concat (no scatter), because the LS tick that calls it is
    traced into the Pallas rollout kernels (ARCHITECTURE §2); outputs
    come back in ``occ``'s dtype, value-identical to the bool form."""
    L = occ.shape[-1]
    o = occ.astype(jnp.int32)
    zero = jnp.zeros_like(o[..., :1])
    g = 1 - o                                 # suffix-OR of free cells
    s = 1
    while s < L:
        g = g | jnp.concatenate([g[..., s:], jnp.zeros_like(g[..., :s])],
                                axis=-1)
        s *= 2
    gap = jnp.concatenate([g[..., 1:], zero], axis=-1)   # free cell ahead
    moved = o & (gap | can_cross.astype(jnp.int32)[..., None])
    stay = o & (1 - moved)
    shifted = jnp.concatenate([zero, moved[..., :-1]], axis=-1)
    return ((stay | shifted).astype(occ.dtype), moved.astype(occ.dtype),
            moved[..., L - 1].astype(occ.dtype))


# directions: 0 south(+i), 1 north(-i), 2 west(-j), 3 east(+j)
_DI = (1, -1, 0, 0)
_DJ = (0, 0, -1, 1)


def local_traffic_state(state: TrafficState, i, j) -> LocalTrafficState:
    """Local view of a global state at intersection (i, j). ``i``/``j`` may
    be traced, so this vmaps over a vector of agent coordinates."""
    return LocalTrafficState(lanes=state.lanes[i, j], phase=state.phase[i, j])


def make_multi_traffic_env(cfg: TrafficConfig, agents) -> Env:
    """GS with an agent at every listed intersection.

    ``agents``: (A, 2) int array of (i, j) coordinates. ``step`` takes (A,)
    actions; obs / reward / info leaves carry a leading agent axis.
    """
    G, L = cfg.grid, cfg.lane_len
    agents = jnp.asarray(agents, jnp.int32)
    A = agents.shape[0]
    ais, ajs = agents[:, 0], agents[:, 1]
    agent_mask = jnp.zeros((G, G), bool).at[ais, ajs].set(True)
    M = 8 if cfg.ext_influence else 4
    spec = EnvSpec(name="traffic-gs-multi", obs_dim=4 * L + 1, n_actions=2,
                   n_influence=M, dset_dim=4 * L, dset_full_dim=4 * L + 1,
                   n_agents=A)

    def observe(state: TrafficState):
        def one(i, j):
            local = state.lanes[i, j].reshape(-1).astype(jnp.float32)
            return jnp.concatenate(
                [local, state.phase[i, j][None].astype(jnp.float32)])
        return jax.vmap(one)(ais, ajs)

    def reset(key):
        k1, k2 = jax.random.split(key)
        lanes = jax.random.bernoulli(k1, 0.15, (G, G, 4, L))
        phase = jax.random.randint(k2, (G, G), 0, 2).astype(jnp.int8)
        return TrafficState(lanes=lanes, phase=phase,
                            timer=jnp.zeros((G, G), jnp.int32))

    def step(state: TrafficState, actions, key):
        lanes, phase, timer = state
        phase = phase.at[ais, ajs].set(actions.astype(jnp.int8))
        green = _green(phase, G)

        # crossing feasibility: downstream tail must be free (edges exit)
        dest_free = jnp.ones((G, G, 4), bool)
        for d in range(4):
            tails = lanes[:, :, d, 0]
            rolled = jnp.roll(tails, shift=(-_DI[d], -_DJ[d]), axis=(0, 1))
            free = ~rolled
            if d == 0:
                free = free.at[G - 1, :].set(True)
            elif d == 1:
                free = free.at[0, :].set(True)
            elif d == 2:
                free = free.at[:, 0].set(True)
            else:
                free = free.at[:, G - 1].set(True)
            dest_free = dest_free.at[:, :, d].set(free)

        new_lanes, moved, crossed = _advance_lane(lanes, green & dest_free)

        # injections: crossings arriving from upstream, else boundary inflow
        inj = jnp.zeros((G, G, 4), bool)
        key, kin = jax.random.split(key)
        inflow = jax.random.bernoulli(kin, cfg.p_in, (G, G, 4))
        for d in range(4):
            arriving = jnp.roll(crossed[:, :, d], shift=(_DI[d], _DJ[d]),
                                axis=(0, 1))
            boundary = jnp.zeros((G, G), bool)
            if d == 0:
                arriving = arriving.at[0, :].set(False)
                boundary = boundary.at[0, :].set(True)
            elif d == 1:
                arriving = arriving.at[G - 1, :].set(False)
                boundary = boundary.at[G - 1, :].set(True)
            elif d == 2:
                arriving = arriving.at[:, G - 1].set(False)
                boundary = boundary.at[:, G - 1].set(True)
            else:
                arriving = arriving.at[:, 0].set(False)
                boundary = boundary.at[:, 0].set(True)
            inj = inj.at[:, :, d].set(
                arriving | (boundary & inflow[:, :, d]))
        tail_free = ~new_lanes[:, :, :, 0]
        inj = inj & tail_free
        new_lanes = new_lanes.at[:, :, :, 0].set(
            new_lanes[:, :, :, 0] | inj)

        # actuated controllers (non-agent intersections)
        q = lanes[:, :, :, L - cfg.queue_window:].sum(-1)       # (G,G,4)
        q_ns, q_ew = q[..., 0] + q[..., 1], q[..., 2] + q[..., 3]
        green_q = jnp.where(phase == 0, q_ns, q_ew)
        red_q = jnp.where(phase == 0, q_ew, q_ns)
        want_switch = (red_q > green_q) & (timer >= cfg.min_phase)
        new_phase = jnp.where(want_switch, 1 - phase, phase).astype(jnp.int8)
        new_timer = jnp.where(want_switch, 0, timer + 1)
        new_phase = jnp.where(agent_mask, phase, new_phase).astype(jnp.int8)
        new_timer = jnp.where(agent_mask, 0, new_timer)

        new_state = TrafficState(lanes=new_lanes, phase=new_phase,
                                 timer=new_timer)

        def view(i, j):
            # reward: average speed over this agent's incoming lanes
            n_cars = lanes[i, j].sum()
            n_moved = moved[i, j].sum()
            reward = jnp.where(n_cars > 0,
                               n_moved / jnp.maximum(n_cars, 1), 1.0)
            dset = lanes[i, j].reshape(-1).astype(jnp.float32)   # x_t
            u = inj[i, j].astype(jnp.float32)                    # u_t (4,)
            if cfg.ext_influence:
                u = jnp.concatenate(
                    [u, (~dest_free[i, j]).astype(jnp.float32)])
            obs = jnp.concatenate(
                [new_lanes[i, j].reshape(-1).astype(jnp.float32),
                 new_phase[i, j][None].astype(jnp.float32)])
            info = {
                "u": u,
                "dset": dset,
                "dset_full": jnp.concatenate(
                    [dset, phase[i, j][None].astype(jnp.float32)]),
                "n_cars": n_cars,
            }
            return obs, reward, info

        obs, reward, info = jax.vmap(view)(ais, ajs)
        return new_state, obs, reward, info

    return Env(spec=spec, reset=reset, step=step, observe=observe)


def make_batched_multi_traffic_env(cfg: TrafficConfig,
                                   agents) -> BatchedEnv:
    """Natively batched multi-agent GS: B whole G x G grids advance as one
    vectorized program — state leaves carry a leading (B,) env axis, every
    tick draws its boundary inflow with a single bulk Bernoulli call, and
    per-agent extraction is one vmap over the agent list (out axis 1, so
    obs/reward/info leaves are (B, A, ...)).

    Same dynamics as ``make_multi_traffic_env``; with ``p_in == 0`` (the
    only internal randomness switched off) the two agree exactly, which is
    what the engine-vs-engine parity tests pin down. This is what makes
    the ``gs-multi`` benchmark row an engine-vs-engine comparison instead
    of engine-vs-vmap-of-scalar."""
    G, L = cfg.grid, cfg.lane_len
    agents = jnp.asarray(agents, jnp.int32)
    A = agents.shape[0]
    ais, ajs = agents[:, 0], agents[:, 1]
    agent_mask = jnp.zeros((G, G), bool).at[ais, ajs].set(True)
    M = 8 if cfg.ext_influence else 4
    spec = EnvSpec(name="traffic-gs-multi-b", obs_dim=4 * L + 1,
                   n_actions=2, n_influence=M, dset_dim=4 * L,
                   dset_full_dim=4 * L + 1, n_agents=A)

    def observe(state: TrafficState):
        B = state.lanes.shape[0]

        def one(i, j):
            local = state.lanes[:, i, j].reshape(B, -1).astype(jnp.float32)
            return jnp.concatenate(
                [local, state.phase[:, i, j, None].astype(jnp.float32)],
                axis=-1)

        return jax.vmap(one, out_axes=1)(ais, ajs)      # (B, A, obs)

    def reset(key, n_envs: int):
        k1, k2 = jax.random.split(key)
        lanes = jax.random.bernoulli(k1, 0.15, (n_envs, G, G, 4, L))
        phase = jax.random.randint(k2, (n_envs, G, G), 0, 2
                                   ).astype(jnp.int8)
        return TrafficState(lanes=lanes, phase=phase,
                            timer=jnp.zeros((n_envs, G, G), jnp.int32))

    def noise_fn(key, n_envs: int):
        kin = jax.random.split(key)[1]
        return jax.random.bernoulli(kin, cfg.p_in, (n_envs, G, G, 4))

    def step_det(state: TrafficState, actions, inflow):
        lanes, phase, timer = state       # (B,G,G,4,L), (B,G,G), (B,G,G)
        B = lanes.shape[0]
        phase = phase.at[:, ais, ajs].set(actions.astype(jnp.int8))
        green = _green(phase, G)                         # (B, G, G, 4)

        # crossing feasibility: downstream tail must be free (edges exit)
        dest_free = jnp.ones((B, G, G, 4), bool)
        for d in range(4):
            tails = lanes[:, :, :, d, 0]
            rolled = jnp.roll(tails, shift=(-_DI[d], -_DJ[d]), axis=(1, 2))
            free = ~rolled
            if d == 0:
                free = free.at[:, G - 1, :].set(True)
            elif d == 1:
                free = free.at[:, 0, :].set(True)
            elif d == 2:
                free = free.at[:, :, 0].set(True)
            else:
                free = free.at[:, :, G - 1].set(True)
            dest_free = dest_free.at[:, :, :, d].set(free)

        new_lanes, moved, crossed = _advance_lane(lanes, green & dest_free)

        # injections: crossings arriving from upstream, else boundary
        # inflow — drawn for the whole batch in ``noise_fn``
        inj = jnp.zeros((B, G, G, 4), bool)
        for d in range(4):
            arriving = jnp.roll(crossed[:, :, :, d],
                                shift=(_DI[d], _DJ[d]), axis=(1, 2))
            boundary = jnp.zeros((G, G), bool)
            if d == 0:
                arriving = arriving.at[:, 0, :].set(False)
                boundary = boundary.at[0, :].set(True)
            elif d == 1:
                arriving = arriving.at[:, G - 1, :].set(False)
                boundary = boundary.at[G - 1, :].set(True)
            elif d == 2:
                arriving = arriving.at[:, :, G - 1].set(False)
                boundary = boundary.at[:, G - 1].set(True)
            else:
                arriving = arriving.at[:, :, 0].set(False)
                boundary = boundary.at[:, 0].set(True)
            inj = inj.at[:, :, :, d].set(
                arriving | (boundary & inflow[:, :, :, d]))
        tail_free = ~new_lanes[:, :, :, :, 0]
        inj = inj & tail_free
        new_lanes = new_lanes.at[:, :, :, :, 0].set(
            new_lanes[:, :, :, :, 0] | inj)

        # actuated controllers (non-agent intersections)
        q = lanes[:, :, :, :, L - cfg.queue_window:].sum(-1)   # (B,G,G,4)
        q_ns, q_ew = q[..., 0] + q[..., 1], q[..., 2] + q[..., 3]
        green_q = jnp.where(phase == 0, q_ns, q_ew)
        red_q = jnp.where(phase == 0, q_ew, q_ns)
        want_switch = (red_q > green_q) & (timer >= cfg.min_phase)
        new_phase = jnp.where(want_switch, 1 - phase,
                              phase).astype(jnp.int8)
        new_timer = jnp.where(want_switch, 0, timer + 1)
        new_phase = jnp.where(agent_mask, phase, new_phase).astype(jnp.int8)
        new_timer = jnp.where(agent_mask, 0, new_timer)

        new_state = TrafficState(lanes=new_lanes, phase=new_phase,
                                 timer=new_timer)

        def view(i, j):
            n_cars = lanes[:, i, j].sum(axis=(1, 2))
            n_moved = moved[:, i, j].sum(axis=(1, 2))
            reward = jnp.where(n_cars > 0,
                               n_moved / jnp.maximum(n_cars, 1), 1.0)
            dset = lanes[:, i, j].reshape(B, -1).astype(jnp.float32)
            u = inj[:, i, j].astype(jnp.float32)
            if cfg.ext_influence:
                u = jnp.concatenate(
                    [u, (~dest_free[:, i, j]).astype(jnp.float32)],
                    axis=-1)
            obs = jnp.concatenate(
                [new_lanes[:, i, j].reshape(B, -1).astype(jnp.float32),
                 new_phase[:, i, j, None].astype(jnp.float32)], axis=-1)
            info = {
                "u": u,
                "dset": dset,
                "dset_full": jnp.concatenate(
                    [dset, phase[:, i, j, None].astype(jnp.float32)],
                    axis=-1),
                "n_cars": n_cars,
            }
            return obs, reward, info

        obs, reward, info = jax.vmap(view, out_axes=1)(ais, ajs)
        return new_state, obs, reward, info

    def step(state: TrafficState, actions, key):
        return step_det(state, actions,
                        noise_fn(key, state.lanes.shape[0]))

    return BatchedEnv(spec=spec, reset=reset, step=step, observe=observe,
                      noise_fn=noise_fn, step_det=step_det)


def make_traffic_env(cfg: TrafficConfig = TrafficConfig()):
    """Single-agent GS: the multi-agent env at ``cfg.agent``, squeezed."""
    multi = make_multi_traffic_env(cfg, jnp.array([cfg.agent], jnp.int32))
    return squeeze_agent_env(multi, "traffic-gs")


def make_local_traffic_env(cfg: TrafficConfig = TrafficConfig()):
    """LS: the agent's 4 incoming lanes; u_t drives boundary injection (and,
    with ``ext_influence``, blocks crossing on congested downstream tails)."""
    L = cfg.lane_len
    M = 8 if cfg.ext_influence else 4
    spec = EnvSpec(name="traffic-ls", obs_dim=4 * L + 1, n_actions=2,
                   n_influence=M, dset_dim=4 * L, dset_full_dim=4 * L + 1)

    def observe(state: LocalTrafficState):
        return jnp.concatenate(
            [state.lanes.reshape(-1).astype(jnp.float32),
             state.phase[None].astype(jnp.float32)])

    def reset(key):
        lanes = jax.random.bernoulli(key, 0.15, (4, L))
        return LocalTrafficState(lanes=lanes, phase=jnp.int8(0))

    def step(state: LocalTrafficState, action, u, key):
        lanes = state.lanes
        phase = action.astype(jnp.int8)
        ns = (phase == 0)
        green = jnp.stack([ns, ns, ~ns, ~ns])                    # (4,)
        # crossing cars exit the local region freely (open boundary) unless
        # the 8-bit u_t marks the downstream tail as occupied
        can_cross = green
        if cfg.ext_influence:
            can_cross = green & ~u[4:].astype(bool)
        new_lanes, moved, _ = _advance_lane(lanes, can_cross)
        inj = u[:4].astype(bool) & ~new_lanes[:, 0]
        new_lanes = new_lanes.at[:, 0].set(new_lanes[:, 0] | inj)

        n_cars = lanes.sum()
        n_moved = moved.sum()
        reward = jnp.where(n_cars > 0, n_moved / jnp.maximum(n_cars, 1), 1.0)
        new_state = LocalTrafficState(lanes=new_lanes, phase=phase)
        dset = lanes.reshape(-1).astype(jnp.float32)
        info = {"dset": dset,
                "dset_full": jnp.concatenate(
                    [dset, state.phase[None].astype(jnp.float32)]),
                "n_cars": n_cars}
        return new_state, observe(new_state), reward, info

    def dset_fn(state: LocalTrafficState, action):
        return state.lanes.reshape(-1).astype(jnp.float32)

    return LocalEnv(spec=spec, reset=reset, step=step, observe=observe,
                    dset_fn=dset_fn)


def make_batched_local_traffic_env(
        cfg: TrafficConfig = TrafficConfig()) -> BatchedLocalEnv:
    """Natively batched LS: every leaf carries a leading (B,) env axis and
    one step is one vectorized lane advance for the whole batch — the fused
    IALS rollout engine's transition. Same dynamics as
    ``make_local_traffic_env`` (the traffic LS draws no randomness of its
    own, so batched and vmapped-scalar steps agree exactly, ``noise_fn``
    is leafless, and ``rollout_tick`` — the transition+reward core the
    whole-horizon kernel inlines — is pure boolean lane algebra)."""
    L = cfg.lane_len
    M = 8 if cfg.ext_influence else 4
    spec = EnvSpec(name="traffic-ls-b", obs_dim=4 * L + 1, n_actions=2,
                   n_influence=M, dset_dim=4 * L, dset_full_dim=4 * L + 1)

    # observe / rollout_tick / dset_fn are traced into the Pallas rollout
    # kernels: casts come before every reshape, slice, or concat (Mosaic
    # cannot reshape bool or compare int8 vectors), the lane algebra is
    # int32 0/1, and reductions run one axis at a time (ARCHITECTURE §2)
    def observe(state: LocalTrafficState):
        B = state.lanes.shape[0]
        return jnp.concatenate(
            [state.lanes.astype(jnp.float32).reshape(B, -1),
             state.phase.astype(jnp.float32)[:, None]], axis=-1)

    def reset(key, n_envs: int):
        lanes = jax.random.bernoulli(key, 0.15, (n_envs, 4, L))
        return LocalTrafficState(
            lanes=lanes, phase=jnp.zeros((n_envs,), jnp.int8))

    def noise_fn(key, n_envs: int):
        return None          # the traffic LS is deterministic given u_t

    def rollout_tick(state: LocalTrafficState, actions, u, noise):
        del noise
        occ = state.lanes.astype(jnp.int32)              # (B, 4, L)
        ns = (actions == 0).astype(jnp.int32)[:, None]   # phase 0 = NS
        green = jnp.concatenate([ns, ns, 1 - ns, 1 - ns], axis=-1)
        can_cross = green                                # (B, 4)
        if cfg.ext_influence:
            can_cross = green & (u[:, 4:] == 0).astype(jnp.int32)
        new, moved, _ = _advance_lane(occ, can_cross)
        inj = (u[:, :4] != 0).astype(jnp.int32) & (1 - new[:, :, 0])
        new = new | jnp.concatenate(
            [inj[:, :, None], jnp.zeros_like(new[:, :, 1:])], axis=-1)

        n_cars = occ.sum(-1).sum(-1)
        n_moved = moved.sum(-1).sum(-1)
        reward = jnp.where(n_cars > 0,
                           n_moved / jnp.maximum(n_cars, 1), 1.0)
        return LocalTrafficState(lanes=new.astype(state.lanes.dtype),
                                 phase=actions.astype(jnp.int8)), reward

    def step_det(state: LocalTrafficState, actions, u, noise):
        new_state, reward = rollout_tick(state, actions, u, noise)
        lanes = state.lanes
        B = lanes.shape[0]
        dset = lanes.reshape(B, -1).astype(jnp.float32)
        info = {"dset": dset,
                "dset_full": jnp.concatenate(
                    [dset, state.phase[:, None].astype(jnp.float32)],
                    axis=-1),
                "n_cars": lanes.sum(axis=(1, 2))}
        return new_state, observe(new_state), reward, info

    def step(state: LocalTrafficState, actions, u, key):
        return step_det(state, actions, u,
                        noise_fn(key, state.lanes.shape[0]))

    def dset_fn(state: LocalTrafficState, actions):
        B = state.lanes.shape[0]
        return state.lanes.astype(jnp.float32).reshape(B, -1)

    return BatchedLocalEnv(spec=spec, reset=reset, step=step,
                           observe=observe, dset_fn=dset_fn,
                           noise_fn=noise_fn, step_det=step_det,
                           rollout_tick=rollout_tick,
                           # astype + reshape + concat only: already
                           # kernel-safe, so the policy-rollout kernel
                           # traces the real observe
                           obs_fn=observe)
