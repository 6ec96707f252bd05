"""The unified IALS rollout engine: one implementation, every variant.

``make_unified_ials`` builds the fused, natively batched IALS
(`BatchedEnv`) for ANY point of the paper's simulator grid:

    backbone      x  agent multiplicity  x  AIP variant
    {gru, fnn}       {single A=1, multi}    {trained, untrained, F-IALS}

Single- and multi-agent are not separate engines any more (they were, in
PRs 2-3): the agent axis is just another batch dimension of one fused
tick, and — following the batched-simulation playbook of Shacklett et
al. 2021 — just another *grid dimension* of one whole-horizon rollout
kernel. Single-agent is the A=1 squeeze, mirroring how the env layer
squeezes its 1-agent multi envs.

One tick for the whole (B,) env batch (times A agents) = one bulk uint32
bits draw, one fused AIP step (``core.influence``'s multi-agent steps —
``kernels/aip_step.py`` on TPU; per backbone, whichever of the stacked
/ vmapped formulations measures faster off-TPU), one vectorized LS
transition over all B·A lanes. State leaves are (B, ...) when A=1 and
(B, A, ...) otherwise; PPO consumes either shape as extra batch
dimensions.

Whole-horizon layer (``noise_fn`` / ``step_det`` / ``rollout`` /
``policy_rollout`` — see ``envs/api.py`` and docs/ARCHITECTURE.md):
``rollout`` advances all T ticks in one call, and ``policy_rollout``
goes one level further — the PPO actor joins the loop (policy forward,
Gumbel-argmax actions, episode resets traced in alongside the AIP+LS
tick), so an entire acting horizon is one ``kernels.ops.policy_rollout``
dispatch; the slot is set only when the kernel route is active (TPU, or
``use_horizon_kernel=True``), since off-TPU PPO's own hoisted scan is
the bit-identical default. When the AIP is real (not a fixed marginal) and the
LS exposes ``rollout_tick``, that is ONE kernel-route dispatch —
``kernels.ops.ials_rollout_multi`` (GRU) or ``kernels.ops.fnn_rollout``
(FNN) — with the AIP recurrent state and every LS leaf VMEM-resident
across the horizon on TPU, and the identical-math stacked oracle scan
elsewhere; lanes are reordered agent-major ((A·B,) with lane ``a*B+b``)
at the boundary so each kernel lane block indexes its own agent's
weights, and bool/int8 leaves round-trip through int32 via
``envs.api.kernel_codec``. Otherwise ``rollout`` is a bulk-noise scan of
the fused per-tick step. Every path is bitwise-equal to scanning
``step`` with the same keys (``env_rollout``'s contract; enforced by
tests/test_rollout_engine.py for all backbone x multiplicity combos).

``make_batched_ials`` / ``make_batched_multi_ials`` are thin wrappers
kept as the historical entry points.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import influence
from repro.envs.api import (BatchedEnv, BatchedLocalEnv, horizon_noise,
                            kernel_codec)
from repro.nn.act import fast_sigmoid, uniform_from_bits


class IALSState(NamedTuple):
    ls_state: object      # LS state; (B, ...) leaves, (B, A, ...) if multi
    aip_state: jax.Array  # (B, [A,] H) GRU hidden / (B, [A,] stack, d_in)


def kernel_fns(local_env: BatchedLocalEnv, ls_def, ls_dtypes, nz_def,
               nz_dtypes):
    """-> (tick, dset, obs): the LS's ``rollout_tick`` / ``dset_fn`` /
    ``obs_fn`` on the encoded leaf tuples the rollout kernels carry
    (``envs.api.kernel_codec``; ``*_def`` / ``*_dtypes`` describe the
    flattened LS state and noise pytrees) — what the kernels and their
    ``kernels/ref.py`` oracles trace per tick."""
    ls_enc, ls_dec = kernel_codec(ls_def, ls_dtypes)
    _, nz_dec = kernel_codec(nz_def, nz_dtypes)

    def tick(vals, a, u, nzv):
        st2, r = local_env.rollout_tick(ls_dec(vals), a, u, nz_dec(nzv))
        return ls_enc(jax.tree_util.tree_leaves(st2)), r

    def dset(vals, a):
        return local_env.dset_fn(ls_dec(vals), a)

    def obs(vals):
        return local_env.obs_fn(ls_dec(vals))

    return tick, dset, obs


def _check_stateless(stateless, fixed_marginal, fixed_marginal_vec):
    if stateless and fixed_marginal is None and fixed_marginal_vec is None:
        raise ValueError(
            "stateless=True only makes sense for the F-IALS (fixed "
            "marginal) variants: a trained/untrained AIP needs its "
            "recurrent state advanced every tick")


def make_unified_ials(local_env: BatchedLocalEnv, aip_params,
                      aip_cfg: influence.AIPConfig, *,
                      n_agents: int = 1,
                      fixed_marginal: Optional[float] = None,
                      fixed_marginal_vec=None,
                      stateless: bool = False,
                      use_horizon_kernel: Optional[bool] = None,
                      mesh=None) -> BatchedEnv:
    """The unified fused rollout engine — a natively batched IALS for any
    backbone x multiplicity combination.

    ``local_env`` is a natively batched LS; with ``n_agents = A > 1`` its
    (B·A,)-lane batch axis carries every agent of every env copy
    (``aip_params`` leaves are (A, ...) stacked — one AIP per agent) and
    the engine exposes the multi-agent ``BatchedEnv`` signature PPO
    consumes: actions (B, A), obs (B, A, obs_dim). With ``n_agents=1``
    the agent axis is squeezed off every leaf and ``aip_params`` is a
    plain single-AIP pytree.

    ``fixed_marginal`` (scalar) / ``fixed_marginal_vec`` ((M,) shared or
    (A, M) per-agent) switch every simulator into F-IALS mode (App. E);
    ``stateless=True`` (F-IALS only) freezes the ignored AIP state at its
    init value — the leaf is kept for state-shape parity (checkpoints,
    donated PPO buffers, and scan carries stay interchangeable across
    variants), at the cost that the frozen leaf is not a warmed-up
    recurrent state: swap simulators only at reset boundaries.

    ``use_horizon_kernel`` overrides the ``rollout`` backend
    auto-detection (None = the kernel route on TPU, the bulk-noise scan
    elsewhere): True forces the ``kernels.ops`` route off-TPU too (on CPU
    that is the stacked oracle scan — the parity tests cover the kernel
    glue that way), False pins the scan.

    ``mesh`` (a ``jax.sharding.Mesh``) turns on SPMD partitioning: state
    entering and leaving ``step_det`` / ``rollout`` / ``policy_rollout``
    is pinned to the IALS rules of ``distributed/sharding.py`` (env lanes
    over the data axes, the agent axis over "model" when it divides) via
    ``with_sharding_constraint``, and GSPMD propagates through the
    horizon. The kernel route of ``rollout`` / ``policy_rollout`` runs
    under ``shard_map`` on the same rules instead, each device on its own
    lanes (GSPMD cannot split a Pallas call; it would gather the lanes
    around it). ``reset`` stays unconstrained on purpose: constraining its
    output back-propagates the sharding into the threefry RNG lowering
    and changes the drawn bits — shard fresh states eagerly with
    ``sharding.shard_ials_state`` instead. ``mesh=None`` (or a size-1
    mesh) adds no constraint ops — the default program is bitwise
    unchanged — and data-parallel lane sharding introduces no cross-lane
    reductions, so the sharded rollout is bitwise-equal to the
    single-device one (tests/test_sharding.py).
    """
    _check_stateless(stateless, fixed_marginal, fixed_marginal_vec)
    if mesh is not None:
        from repro.distributed import sharding as _shd
        if _shd.mesh_size(mesh) == 1:
            mesh = None

    def _constrain(state: "IALSState") -> "IALSState":
        if mesh is None:
            return state
        from repro.distributed import sharding as _shd
        return _shd.constrain_ials_state(state, mesh, n_agents)

    A = n_agents
    multi = A > 1
    M = local_env.spec.n_influence
    spec = dataclasses.replace(
        local_env.spec,
        name=local_env.spec.name + ("+multi-ials" if multi else "+ials"),
        n_agents=A)
    ash = (A,) if multi else ()
    if fixed_marginal_vec is not None:
        marg = jnp.broadcast_to(
            jnp.asarray(fixed_marginal_vec, jnp.float32), ash + (M,))
    elif fixed_marginal is not None:
        marg = jnp.full(ash + (M,), fixed_marginal, jnp.float32)
    else:
        marg = None

    tmap = jax.tree_util.tree_map

    # (B, A, ...) <-> (B*A, ...) batch-major — the LS's native lane order
    def _flat(tree, B):
        if not multi:
            return tree
        return tmap(lambda l: l.reshape((B * A,) + l.shape[2:]), tree)

    def _unflat(tree, B):
        if not multi:
            return tree
        return tmap(lambda l: l.reshape((B, A) + l.shape[1:]), tree)

    def reset(key, n_envs: int):
        # NOT constrained: a sharding constraint here back-propagates into
        # the threefry lowering of the LS's random init draws and changes
        # the drawn bits (jax_threefry_partitionable=False), breaking the
        # sharded-vs-single-device bitwise contract. Eager placement is
        # ``sharding.shard_ials_state``'s job; the in-horizon constraints
        # (step_det / rollout / policy_rollout) are the bitwise-safe ones.
        return IALSState(
            ls_state=_unflat(local_env.reset(key, n_envs * A), n_envs),
            aip_state=influence.init_state(aip_cfg, (n_envs,) + ash))

    def _batch(state: IALSState) -> int:
        return jax.tree_util.tree_leaves(state.ls_state)[0].shape[0]

    def noise_fn(key, n_envs: int):
        k_u, k_env = jax.random.split(key)
        bits = jax.random.bits(k_u, (n_envs,) + ash + (M,), jnp.uint32)
        env = (local_env.noise_fn(k_env, n_envs * A)
               if local_env.noise_fn is not None else k_env)
        return {"bits": bits, "env": env}

    def _ls_step(ls_flat, a_flat, u_flat, env_noise):
        if local_env.step_det is not None:
            return local_env.step_det(ls_flat, a_flat, u_flat, env_noise)
        return local_env.step(ls_flat, a_flat, u_flat, env_noise)

    def step_det(state: IALSState, actions, noise):
        B = actions.shape[0]
        ls_flat = _flat(state.ls_state, B)
        a_flat = actions.reshape((B * A,)) if multi else actions
        d_t = local_env.dset_fn(ls_flat, a_flat)       # (B·A, Dd)
        if multi:
            d_t = d_t.reshape(B, A, -1)
        bits = noise["bits"]
        if marg is None:
            sample = (influence.step_sample_multi if multi
                      else influence.step_sample)
            logits, new_aip, u = sample(aip_params, aip_cfg,
                                        state.aip_state, d_t, bits)
            probs = fast_sigmoid(logits)
        else:
            if stateless:
                new_aip = state.aip_state
            else:
                fwd = influence.step_multi if multi else influence.step
                _, new_aip = fwd(aip_params, aip_cfg, state.aip_state,
                                 d_t)
            probs = jnp.broadcast_to(marg, (B,) + ash + (M,))
            u = (uniform_from_bits(bits) < probs).astype(jnp.float32)
        u_flat = u.reshape(B * A, M) if multi else u
        ls2, obs, r, info = _ls_step(ls_flat, a_flat, u_flat,
                                     noise["env"])
        info = dict(_unflat(info, B))
        info["u"] = u
        info["u_probs"] = probs
        if multi:
            obs, r = obs.reshape(B, A, -1), r.reshape(B, A)
        return _constrain(IALSState(ls_state=_unflat(ls2, B),
                                    aip_state=new_aip)), obs, r, info

    def step(state: IALSState, actions, key):
        return step_det(state, actions, noise_fn(key, actions.shape[0]))

    # --- whole-horizon path -------------------------------------------
    # agent-major lane layout at the kernel boundary: lane a*B + b, so
    # each kernel lane block belongs to one agent and indexes that
    # agent's stacked weights (no-ops when A == 1). The agent count is
    # read off the shapes: under a mesh these run per device, on that
    # device's share of the agents.
    def _lane_fold(x):                    # (B, A, ...) -> (A·B, ...)
        if not multi:
            return x
        return x.swapaxes(0, 1).reshape((-1,) + x.shape[2:])

    def _lane_unfold(x, B):               # (A·B, ...) -> (B, A, ...)
        if not multi:
            return x
        return x.reshape((-1, B) + x.shape[1:]).swapaxes(0, 1)

    def _stream_fold(x):                  # (T, B, A, ...) -> (T, A·B, ...)
        if not multi:
            return x
        return x.swapaxes(1, 2).reshape((x.shape[0], -1) + x.shape[3:])

    def _stream_unfold(x, B):             # (T, A·B, ...) -> (T, B, A, ...)
        if not multi:
            return x
        return x.reshape((x.shape[0], -1, B) + x.shape[2:]).swapaxes(1, 2)

    def _env_noise(noise, B):
        """The LS noise leaves are (T, B·A, ...) batch-major; split the
        agent axis out so they shard and fold like every other stream."""
        if not multi:
            return noise
        return {"bits": noise["bits"], "env": tmap(
            lambda l: l.reshape((l.shape[0], B, A) + l.shape[2:]),
            noise["env"])}

    def _lane_parallel(fn, B, in_kinds, out_kinds, *args):
        """Run the kernel route ``fn`` on each device's own env lanes. A
        Pallas call is a custom call that GSPMD cannot partition, so on
        a mesh the route is a ``shard_map`` under the IALS rules of
        ``distributed/sharding.py`` (``ials_kernel_specs`` names each
        operand kind) and ``fn`` sees the local (B/n, ...) leaves. No
        lane crosses a device, so the result is the single-device one."""
        if mesh is None:
            return fn(*args)
        from repro.distributed import sharding as _shd
        spec = _shd.ials_kernel_specs(B, A, mesh)
        return jax.shard_map(
            fn, mesh=mesh, in_specs=tuple(spec[k] for k in in_kinds),
            out_specs=tuple(spec[k] for k in out_kinds),
            check_vma=False)(*args)

    _kernel_fns = {}      # structural key -> stable (tick, dset, obs)
    #                       closures (stable identity keeps the kernel's
    #                       jit cache warm across rollout calls)

    def _kernel_closures(ls_def, ls_dtypes, nz_def, nz_dtypes):
        key_ = (ls_def, ls_dtypes, nz_def, nz_dtypes)
        if key_ not in _kernel_fns:
            _kernel_fns[key_] = kernel_fns(local_env, *key_)
        return _kernel_fns[key_]

    def _stacked(tree):
        """aip_params with a leading (A,) axis on every leaf (the A=1
        squeeze stacks on the fly)."""
        return tree if multi else tmap(lambda l: l[None], tree)

    def _kernel_operands(state, env_noise):
        """Lane-fold, flatten and encode the LS state and noise leaves
        -> (encoded ls leaves, encoded noise leaves, ls encode, ls
        decode, tick, dset, obs)."""
        ls_leaves, ls_def = jax.tree_util.tree_flatten(
            tmap(_lane_fold, state.ls_state))
        nz_leaves, nz_def = jax.tree_util.tree_flatten(
            tmap(_stream_fold, env_noise))
        ls_dtypes = tuple(l.dtype for l in ls_leaves)
        nz_dtypes = tuple(l.dtype for l in nz_leaves)
        fns = _kernel_closures(ls_def, ls_dtypes, nz_def, nz_dtypes)
        ls_enc, ls_dec = kernel_codec(ls_def, ls_dtypes)
        nz_enc, _ = kernel_codec(nz_def, nz_dtypes)
        return (ls_enc(ls_leaves), nz_enc(nz_leaves), ls_enc, ls_dec) + fns

    def _kernel_rollout(state: IALSState, actions, noise, p):
        from repro.kernels import ops  # deferred: keeps kernels
        #                                optional for the scan path
        B = _batch(state)
        ls, nz, _, ls_dec, k_tick, k_dset, _ = _kernel_operands(
            state, noise["env"])
        acts = _stream_fold(actions)                   # (T, A·B)
        bits = _stream_fold(noise["bits"])             # (T, A·B, M)
        n_ag = acts.shape[1] // B
        if aip_cfg.kind == "gru":
            g, hd = p["gru"], p["head"]
            final, sT, rews = ops.ials_rollout_multi(
                ls, _lane_fold(state.aip_state), g["wx"], g["wh"], g["b"],
                hd["w"], hd["b"], acts, bits, nz, n_agents=n_ag,
                tick_fn=k_tick, dset_fn=k_dset)
            aip_T = _lane_unfold(sT, B)
        else:
            buf0 = _lane_fold(state.aip_state)         # (L, stack, d_in)
            L = buf0.shape[0]
            final, sT, rews = ops.fnn_rollout(
                ls, buf0.reshape(L, -1), p["l1"]["w"], p["l1"]["b"],
                p["l2"]["w"], p["l2"]["b"], p["head"]["w"],
                p["head"]["b"], acts, bits, nz, n_agents=n_ag,
                tick_fn=k_tick, dset_fn=k_dset)
            aip_T = _lane_unfold(
                sT.reshape(L, aip_cfg.stack, aip_cfg.d_in), B)
        ls_T = tmap(lambda l: _lane_unfold(l, B), ls_dec(final))
        return (IALSState(ls_state=ls_T, aip_state=aip_T),
                _stream_unfold(rews, B))

    def rollout(state: IALSState, actions, keys):
        """(state, actions (T, B[, A]), keys (T,)) -> (state, rewards
        (T, B[, A])): the whole horizon in one call, bitwise-equal to
        scanning ``step``."""
        state = _constrain(state)
        B = _batch(state)
        noise = horizon_noise(noise_fn, keys, B)
        use_kernel = (marg is None
                      and local_env.rollout_tick is not None
                      and local_env.noise_fn is not None
                      and (use_horizon_kernel if use_horizon_kernel
                           is not None
                           else jax.default_backend() == "tpu"))
        if use_kernel:
            state, rews = _lane_parallel(
                _kernel_rollout, B, ("state", "stream", "stream", "agent"),
                ("state", "stream"), state, actions, _env_noise(noise, B),
                _stacked(aip_params))
            return _constrain(state), rews

        def tick(carry, xs):
            a, n = xs
            s, _, r, _ = step_det(carry, a, n)
            return s, r

        return jax.lax.scan(tick, state, (actions, noise), unroll=8)

    # --- actor-in-the-loop path (the training-loop contract) ----------
    # set on the env ONLY when the kernel route is active (TPU, or
    # forced via use_horizon_kernel=True): PPO hands the whole acting
    # loop over; off-TPU by default PPO's own hoisted bulk-noise scan is
    # the bit-identical program, so there is nothing to dispatch to
    kernel_route = (marg is None
                    and local_env.rollout_tick is not None
                    and local_env.noise_fn is not None
                    and local_env.obs_fn is not None
                    and (use_horizon_kernel if use_horizon_kernel
                         is not None
                         else jax.default_backend() == "tpu"))

    def _kernel_policy_rollout(state: IALSState, frames, t_in_ep, gumbel,
                               noise, reset_states, p, pol_params, *,
                               episode_len: int, fast_gates: bool):
        from repro.kernels import ops  # deferred: keeps kernels optional
        B = _batch(state)
        T = gumbel.shape[0]
        ls, nz, ls_enc, ls_dec, k_tick, k_dset, k_obs = _kernel_operands(
            state, noise["env"])
        rls_leaves = jax.tree_util.tree_leaves(
            tmap(_stream_fold, reset_states.ls_state))

        # the deterministic reset schedule: tick i is done iff the
        # episode counter hits episode_len there — exactly the scan
        # path's t >= episode_len given the 0 <= t_in_ep invariant
        ticks = (t_in_ep[None, :] + 1
                 + jnp.arange(T, dtype=jnp.int32)[:, None])
        done_env = (ticks % episode_len) == 0            # (T, B)
        t_out = (t_in_ep + T) % episode_len
        frames_l = _lane_fold(frames)                    # (L, k, d)
        L, stack, d_obs = frames_l.shape
        n_ag = L // B
        # lane a*B + b <-> env b
        done_lanes = jnp.tile(done_env.astype(jnp.int32), (1, n_ag))

        if aip_cfg.kind == "gru":
            aw = (p["gru"]["wx"], p["gru"]["wh"], p["gru"]["b"],
                  p["head"]["w"], p["head"]["b"])
            s0 = _lane_fold(state.aip_state)
        else:
            aw = (p["l1"]["w"], p["l1"]["b"], p["l2"]["w"],
                  p["l2"]["b"], p["head"]["w"], p["head"]["b"])
            s0 = _lane_fold(state.aip_state).reshape(L, -1)
        from repro.rl.ppo import flat_policy_weights  # deferred: no cycle
        fin_ls, sT, fT, x, a, logits, v, r = ops.policy_rollout(
            ls, s0, frames_l.reshape(L, -1), aw,
            flat_policy_weights(pol_params), _stream_fold(gumbel),
            _stream_fold(noise["bits"]), done_lanes, nz,
            ls_enc(rls_leaves), kind=aip_cfg.kind, n_agents=n_ag,
            fast_gates=fast_gates, tick_fn=k_tick, dset_fn=k_dset,
            obs_fn=k_obs)
        ls_T = tmap(lambda l: _lane_unfold(l, B), ls_dec(fin_ls))
        if aip_cfg.kind == "gru":
            aip_T = _lane_unfold(sT, B)
        else:
            aip_T = _lane_unfold(
                sT.reshape(-1, aip_cfg.stack, aip_cfg.d_in), B)
        frames_T = _lane_unfold(fT.reshape(-1, stack, d_obs), B)
        r_u = _stream_unfold(r, B)
        ash_n = 1 if multi else 0
        done_b = jnp.broadcast_to(
            done_env.reshape(done_env.shape + (1,) * ash_n),
            r_u.shape).astype(jnp.float32)
        out = {"x": _stream_unfold(x, B), "a": _stream_unfold(a, B),
               "logits": _stream_unfold(logits, B),
               "v": _stream_unfold(v, B), "r": r_u, "done": done_b}
        return (IALSState(ls_state=ls_T, aip_state=aip_T), frames_T,
                t_out, out)

    def policy_rollout(state: IALSState, frames, t_in_ep, pol_params,
                       gumbel, noise, reset_states, *, episode_len: int,
                       fast_gates: bool):
        """``BatchedEnv.policy_rollout`` (see envs/api.py): T PPO acting
        ticks — policy forward, Gumbel-argmax actions, AIP sample, LS
        tick, reward, periodic resets — as ONE ``kernels.ops`` dispatch
        (the Pallas kernel on TPU, the identical-math oracle scan
        elsewhere; per device on a mesh). All randomness arrives
        pre-drawn: ``gumbel`` (T, B, [A,] n_actions), ``noise`` =
        ``horizon_noise`` of this engine's ``noise_fn``,
        ``reset_states`` = T-stacked ``reset`` results. The episode-reset
        schedule is closed-form from ``t_in_ep`` (invariant: 0 <= t_in_ep
        < episode_len, which PPO's reset logic maintains); resets restore
        the streamed LS leaves and re-zero the AIP state (its init
        value)."""
        state = _constrain(state)
        B = _batch(state)
        body = functools.partial(_kernel_policy_rollout,
                                 episode_len=episode_len,
                                 fast_gates=fast_gates)
        state, frames_T, t_out, out = _lane_parallel(
            body, B, ("state", "state", "lanes", "stream", "stream",
                      "stream", "agent", "rep"),
            ("state", "state", "lanes", "stream"), state, frames, t_in_ep,
            gumbel, _env_noise(noise, B), reset_states,
            _stacked(aip_params), pol_params)
        return _constrain(state), frames_T, t_out, out

    def observe(state: IALSState):
        B = _batch(state)
        obs = local_env.observe(_flat(state.ls_state, B))
        return obs.reshape(B, A, -1) if multi else obs

    return BatchedEnv(spec=spec, reset=reset, step=step, observe=observe,
                      rollout=rollout, noise_fn=noise_fn,
                      step_det=step_det,
                      policy_rollout=(policy_rollout if kernel_route
                                      else None))


def make_batched_ials(local_env: BatchedLocalEnv, aip_params,
                      aip_cfg: influence.AIPConfig, *,
                      fixed_marginal: Optional[float] = None,
                      fixed_marginal_vec=None,
                      stateless: bool = False,
                      use_horizon_kernel: Optional[bool] = None,
                      mesh=None) -> BatchedEnv:
    """Single-agent fused rollout engine — ``make_unified_ials`` at its
    A=1 squeeze (kept as the historical entry point)."""
    return make_unified_ials(local_env, aip_params, aip_cfg, n_agents=1,
                             fixed_marginal=fixed_marginal,
                             fixed_marginal_vec=fixed_marginal_vec,
                             stateless=stateless,
                             use_horizon_kernel=use_horizon_kernel,
                             mesh=mesh)


def make_batched_multi_ials(local_env: BatchedLocalEnv, aip_params,
                            aip_cfg: influence.AIPConfig, n_agents: int,
                            *, fixed_marginal: Optional[float] = None,
                            fixed_marginal_vec=None,
                            stateless: bool = False,
                            use_horizon_kernel: Optional[bool] = None,
                            mesh=None) -> BatchedEnv:
    """Fused Distributed IALS (one IALS + AIP per agent region) —
    ``make_unified_ials`` with the agent axis on (kept as the historical
    entry point). ``aip_params`` leaves are (A, ...) stacked."""
    return make_unified_ials(local_env, aip_params, aip_cfg,
                             n_agents=n_agents,
                             fixed_marginal=fixed_marginal,
                             fixed_marginal_vec=fixed_marginal_vec,
                             stateless=stateless,
                             use_horizon_kernel=use_horizon_kernel,
                             mesh=mesh)
