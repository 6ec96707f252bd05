"""PPO (Schulman et al. 2017) — the paper's RL algorithm (§5.1), pure JAX.

Policies are FNNs over a stack of the last k observations (Appendix F:
"policies are fed with a stack of the last 8 observations" in the warehouse;
k=1 in traffic). One training iteration = vectorised rollout (vmap over
environments, lax.scan over time) + GAE + clipped-objective epochs — a single
jitted program, so it runs identically on a GS, an IALS, or any F-IALS
variant, and shards over the mesh's data axes at scale.

Multi-agent (``PPOConfig.n_agents = A > 1``, parameter-shared): the env emits
(A, ...) per-agent obs/rewards; the agent axis rides along as an extra batch
dimension everywhere — one policy network, T * n_envs * A samples per update.
``shard_rollout`` places the env batch on the mesh ``data`` axis so rollouts
scale across devices.

The training-loop contract (see docs/ARCHITECTURE.md §"training-loop
contract"): when the env exposes the whole-horizon pair
``noise_fn``/``step_det``, the rollout hoists ALL of its randomness out of
the scan — the horizon's env noise (``horizon_noise``), per-tick Gumbel
noise for action sampling (``bulk_gumbel``; ``gumbel_argmax(logits, g)`` is
bitwise-equal to ``jax.random.categorical`` on the same key, which is
exactly how jax itself derives the draw), and the per-tick episode-reset
states — so the scan body is fully deterministic: frame-stack shift +
policy forward + ``step_det`` fuse into one pure-compute tick with zero
in-scan key derivation. When the env additionally provides
``policy_rollout`` (the unified IALS engine sets it when its kernel route
is active), the ENTIRE acting loop — act + AIP + LS + reward + resets —
is handed to the env as one whole-horizon dispatch — bit-identical to
the scan on every leaf except the value stream ``v`` (the fused routes
compute both policy heads as one GEMM, a 1-ulp drift documented in
ARCHITECTURE §4). The scan paths themselves are fully bit-identical;
``PPOConfig.hoist_rollout_noise=False`` is the documented opt-out that
preserves the keyed per-tick derivation exactly.

Learner side: GAE is a log-depth ``lax.associative_scan`` over the affine
recurrence (not a T-step sequential scan); each minibatch epoch gathers
one packed f32 record per sample — the action (as an exact f32 value),
logp, advantage and return, with the frames too where they fit one
128-lane tile beside them — under one permutation, so a gather's
per-index cost is paid once rather than once per field, and streams
contiguous slices through the update scan (no per-minibatch gather
copies); ``train_iteration`` donates its (params, opt_state,
rollout-state) arguments so each PPO iteration updates in place instead
of round-tripping fresh buffers.

Phases: every op of an iteration runs under one ``jax.named_scope``,
which reaches the compiled ops' ``op_name`` (the innermost one counts)
and so names each op's phase in a device trace — ``ppo.noise`` (key
splits, the pre-drawn Gumbel, env and reset noise), ``ppo.rollout`` (the
acting loop and the bootstrap value, whatever implements it),
``ppo.gae`` (GAE and the flatten), ``ppo.shuffle`` (the record build,
each epoch's permutation and gather) and ``ppo.update`` (the minibatch
gradient steps and the epoch loop around them).
"""
from __future__ import annotations

import functools

from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.envs.api import BatchedEnv, Env, as_batched, horizon_noise
from repro.nn.act import fast_tanh
from repro.nn.module import dense_init, dense
from repro.optim.adamw import adamw

_LANES = 128    # one TPU lane tile: the widest minibatch record


@dataclass(frozen=True)
class PPOConfig:
    obs_dim: int
    n_actions: int
    frame_stack: int = 1
    hidden: int = 128
    n_envs: int = 16
    rollout_len: int = 128
    episode_len: int = 256        # periodic env reset (episodic tasks)
    gamma: float = 0.99
    lam: float = 0.95
    clip: float = 0.2
    entropy_coef: float = 0.01
    value_coef: float = 0.5
    lr: float = 3e-4
    epochs: int = 4
    n_minibatches: int = 4
    n_agents: int = 1             # leading agent axis of the env (1 = none)
    fast_gates: bool = True       # rational tanh (nn/act.py) in the policy
    #                               net — the same transcendental diet the
    #                               AIP tick got; False = exact jnp.tanh
    hoist_rollout_noise: bool = True  # pre-draw Gumbel action noise + reset
    #                               states alongside the bulk env noise so
    #                               the rollout scan body is deterministic;
    #                               False = the keyed per-tick derivation,
    #                               preserved exactly (the documented
    #                               opt-out — batches are bitwise-equal
    #                               either way)

    @property
    def agent_shape(self) -> tuple:
        return (self.n_agents,) if self.n_agents > 1 else ()


# ---------------------------------------------------------------------------
# Actor-critic network (FNN on frame-stacked obs)
# ---------------------------------------------------------------------------

def init_policy(cfg: PPOConfig, key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d_in = cfg.obs_dim * cfg.frame_stack
    return {
        "l1": dense_init(k1, d_in, cfg.hidden, bias=True),
        "l2": dense_init(k2, cfg.hidden, cfg.hidden, bias=True),
        "pi": dense_init(k3, cfg.hidden, cfg.n_actions, bias=True,
                         scale=0.01),
        "v": dense_init(k4, cfg.hidden, 1, bias=True, scale=0.1),
    }


def flat_policy_weights(params):
    """The flat ``(w1, b1, w2, b2, piw, pib, vw, vb)`` weight tuple — the
    policy-forward ABI shared by every fused consumer of this network:
    the kernels' ``_policy_cell`` / ``_policy_fwd_ref`` (actor-in-the-loop
    rollout), the unified engine's ``policy_rollout`` wiring, and the
    serving tier's slot forward (``kernels/ops.py::serve_forward``). One
    definition, so a params-layout change cannot silently skew the
    kernel routes."""
    return (params["l1"]["w"], params["l1"]["b"],
            params["l2"]["w"], params["l2"]["b"],
            params["pi"]["w"], params["pi"]["b"],
            params["v"]["w"], params["v"]["b"])


def stack_policy_weights(params_list):
    """Stack N checkpoints' ``flat_policy_weights`` tuples into one
    tuple of (N, ...) arrays — the cross-policy serving ABI consumed by
    ``kernels/ops.py::serve_forward_multi`` (one server, many
    checkpoints: lane p of a packed slot runs checkpoint
    ``policy_index[p]``). All checkpoints must share one architecture
    (same PPOConfig shapes); ``jnp.stack`` raises otherwise. Index 0 of
    every leading axis is ``params_list[0]``, so a one-entry stack is
    the single-policy ABI with a size-1 policy axis."""
    flats = [flat_policy_weights(p) for p in params_list]
    return tuple(jnp.stack(ws) for ws in zip(*flats))


def policy_forward(params, x, *, fast_gates: bool):
    """Actor-critic forward pass. ``fast_gates`` (required — thread
    ``PPOConfig.fast_gates`` so the config stays the single source of
    truth) evaluates the hidden tanh layers with the shared rational
    gates from ``nn/act.py`` (|err| < 1e-4, exact saturation) — the exact
    tanh transcendentals were the last per-tick policy cost the ROADMAP
    flagged on the rollout hot path. Training and rollout use the same
    setting, so PPO optimises exactly the network it acts with."""
    act = fast_tanh if fast_gates else jnp.tanh
    h = act(dense(params["l1"], x))
    h = act(dense(params["l2"], h))
    return dense(params["pi"], h), dense(params["v"], h)[..., 0]


# ---------------------------------------------------------------------------
# Action sampling: the hoisted Gumbel-max derivation
# ---------------------------------------------------------------------------

def bulk_gumbel(keys, shape, dtype=jnp.float32):
    """(T,) keys -> (T,) + shape Gumbel noise, row t being exactly
    ``jax.random.gumbel(keys[t], shape, dtype)`` — the same values
    ``jax.random.categorical(keys[t], logits)`` derives internally, drawn
    for the whole horizon before the rollout scan."""
    return jax.vmap(lambda k: jax.random.gumbel(k, shape, dtype))(keys)


def gumbel_argmax(logits, g):
    """Gumbel-max sampling on pre-drawn noise: bitwise-equal to
    ``jax.random.categorical(key, logits)`` when ``g`` came from
    ``jax.random.gumbel(key, logits.shape, logits.dtype)`` (float addition
    is commutative, and jax's categorical IS argmax(gumbel + logits) —
    pinned by the property test in tests/test_train_engine.py)."""
    return jnp.argmax(logits + g, axis=-1)


# ---------------------------------------------------------------------------
# Vectorised rollout with frame stacking + periodic resets
# ---------------------------------------------------------------------------

class RolloutState(NamedTuple):
    env_state: Any
    frames: jax.Array      # (n_envs, *agent_shape, k, obs_dim)
    t_in_ep: jax.Array     # (n_envs,) int32


def _stack_obs(frames):
    return frames.reshape(frames.shape[:-2] + (-1,))


def init_rollout_state(env, cfg: PPOConfig, key,
                       mesh=None) -> RolloutState:
    benv = as_batched(env)
    env_state = benv.reset(key, cfg.n_envs)
    obs = benv.observe(env_state)
    frames = jnp.zeros((cfg.n_envs,) + cfg.agent_shape
                       + (cfg.frame_stack, cfg.obs_dim))
    frames = frames.at[..., -1, :].set(obs)
    rs = RolloutState(env_state=env_state, frames=frames,
                      t_in_ep=jnp.zeros((cfg.n_envs,), jnp.int32))
    return shard_rollout(rs, mesh, n_agents=cfg.n_agents)


def shard_rollout(rs: RolloutState, mesh,
                  n_agents: int = 1) -> RolloutState:
    """Place the rollout state on the mesh under the IALS partition rules
    (``distributed/sharding.py``): env lanes over the data axes, the
    agent axis (frames' and the engine state's dim 1) co-sharded over
    "model" when it divides, replication fallback otherwise.

    Under jit the computation follows the input sharding, so the whole
    rollout (env steps included) executes data-parallel across devices.
    No-op for ``mesh=None`` or a single-device mesh."""
    if mesh is None:
        return rs
    from repro.distributed import sharding as shd
    return shd.shard_ials_state(rs, mesh, n_agents)


def replicate(tree, mesh):
    """Place policy / optimizer state replicated on the mesh, the sharding
    ``train_iteration`` returns them with, so the second iteration runs
    the first one's program instead of compiling another for a new input
    sharding. No-op for ``mesh=None``."""
    if mesh is None:
        return tree
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(tree, NamedSharding(mesh, PartitionSpec()))


def _split_tick_keys(key, T: int):
    """The per-tick (action, env, reset) keys, pre-split outside the scan —
    the same values the historical in-body ``jax.random.split(k, 3)``
    drew, shared by every rollout path so they stay bitwise-comparable."""
    keys = jax.random.split(key, T)
    k3 = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
    return k3[:, 0], k3[:, 1], k3[:, 2]


def rollout(env, cfg: PPOConfig, params, rs: RolloutState, key):
    """-> (new RolloutState, batch with (T, n_envs, *agent_shape, ...)
    leaves, v_last). The agent axis (if any) is just extra batch
    dimension: one parameter-shared policy acts for every agent of every
    env copy.

    Dispatch, most fused first — every path derives its randomness from
    the same pre-split keys, so the scan paths (2, 3) are bit-identical
    and path 1 matches them on every leaf except the 1-ulp ``v`` value
    stream (see the module docstring):
      1. ``benv.policy_rollout`` (the unified IALS engine sets it when
         its kernel route is active): the whole acting loop — frame
         stack, policy forward, Gumbel-argmax sampling, AIP + LS tick,
         reward, periodic resets — is ONE whole-horizon env dispatch
         (a single Pallas call on TPU).
      2. The hoisted deterministic scan (the off-TPU default when the env
         has ``noise_fn``/``step_det``): Gumbel action noise, env noise,
         and reset states are all pre-drawn, so the body is pure compute
         with zero in-scan key derivation.
      3. ``cfg.hoist_rollout_noise=False`` or no whole-horizon pair: the
         keyed per-tick path (``jax.random.categorical`` + in-scan
         resets; env noise still bulk when available) — the historical
         derivation, preserved exactly.
    """
    benv = as_batched(env)
    whole_horizon = (benv.step_det is not None
                     and benv.noise_fn is not None)
    hoist = cfg.hoist_rollout_noise and whole_horizon

    def finish_tick(rs, x, logits, value, a, env_state, obs, r,
                    reset_state):
        """Everything after the env step — frame update, periodic reset,
        batch row — shared verbatim by the keyed and hoisted bodies so
        they stay bitwise-equal by construction."""
        logp = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                   a[..., None], -1)[..., 0]
        frames = jnp.concatenate(
            [rs.frames[..., 1:, :], obs[..., None, :]], axis=-2)
        t = rs.t_in_ep + 1
        done = t >= cfg.episode_len
        env_state = jax.tree_util.tree_map(
            lambda n, i: jnp.where(
                done.reshape((-1,) + (1,) * (n.ndim - 1)), i, n),
            env_state, reset_state)
        obs0 = benv.observe(env_state)
        frames0 = jnp.zeros_like(frames).at[..., -1, :].set(obs0)
        done_f = done.reshape((-1,) + (1,) * (frames.ndim - 1))
        frames = jnp.where(done_f, frames0, frames)
        t = jnp.where(done, 0, t)

        done_b = jnp.broadcast_to(
            done.reshape((-1,) + (1,) * (r.ndim - 1)), r.shape)
        out = {"x": x, "a": a, "logp": logp, "v": value, "r": r,
               "done": done_b.astype(jnp.float32)}
        return RolloutState(env_state, frames, t), out

    def step_h(carry, xs):
        rs = carry
        g, n, reset_state = xs
        x = _stack_obs(rs.frames)
        logits, value = policy_forward(params, x,
                                       fast_gates=cfg.fast_gates)
        a = gumbel_argmax(logits, g)
        env_state, obs, r, _ = benv.step_det(rs.env_state, a, n)
        return finish_tick(rs, x, logits, value, a, env_state,
                           obs, r, reset_state)

    def step_k(carry, xs):
        rs = carry
        ka, ks, kr = xs
        x = _stack_obs(rs.frames)
        logits, value = policy_forward(params, x,
                                       fast_gates=cfg.fast_gates)
        a = jax.random.categorical(ka, logits)
        if whole_horizon:
            env_state, obs, r, _ = benv.step_det(rs.env_state, a, ks)
        else:
            env_state, obs, r, _ = benv.step(rs.env_state, a, ks)
        reset_state = benv.reset(kr, cfg.n_envs)
        return finish_tick(rs, x, logits, value, a, env_state, obs,
                           r, reset_state)

    with jax.named_scope("ppo.noise"):
        ka, ks, kr = _split_tick_keys(key, cfg.rollout_len)
        if hoist:
            gum = bulk_gumbel(
                ka, (cfg.n_envs,) + cfg.agent_shape + (cfg.n_actions,))
            env_noise = horizon_noise(benv.noise_fn, ks, cfg.n_envs)
            reset_states = jax.vmap(lambda k: benv.reset(k, cfg.n_envs))(kr)
        else:
            env_xs = (horizon_noise(benv.noise_fn, ks, cfg.n_envs)
                      if whole_horizon else ks)

    with jax.named_scope("ppo.rollout"):
        if hoist and benv.policy_rollout is not None:
            rs, batch = _engine_policy_rollout(
                benv, cfg, params, rs, gum, env_noise, reset_states)
        elif hoist:
            rs, batch = lax.scan(step_h, rs,
                                 (gum, env_noise, reset_states))
        else:
            rs, batch = lax.scan(step_k, rs, (ka, env_xs, kr))
        x_last = _stack_obs(rs.frames)
        _, v_last = policy_forward(params, x_last,
                                   fast_gates=cfg.fast_gates)
    return rs, batch, v_last


def _engine_policy_rollout(benv: BatchedEnv, cfg: PPOConfig, params, rs,
                           gum, env_noise, reset_states):
    """Hand the whole acting loop to the env's ``policy_rollout`` (the
    unified engine's fused actor-in-the-loop dispatch) and reassemble the
    PPO batch from its streams. The engine computes logits/values with
    the same policy math, so ``logp`` derived from the streamed logits is
    bitwise-equal to the scan path's."""
    env_state, frames, t_in_ep, out = benv.policy_rollout(
        rs.env_state, rs.frames, rs.t_in_ep, params, gum, env_noise,
        reset_states, episode_len=cfg.episode_len,
        fast_gates=cfg.fast_gates)
    logp = jnp.take_along_axis(jax.nn.log_softmax(out["logits"]),
                               out["a"][..., None], -1)[..., 0]
    batch = {"x": out["x"], "a": out["a"], "logp": logp, "v": out["v"],
             "r": out["r"], "done": out["done"]}
    return RolloutState(env_state, frames, t_in_ep), batch


def gae(batch, v_last, gamma, lam):
    """Generalised advantage estimation as a log-depth parallel scan.

    The recurrence adv_t = delta_t + gamma*lam*nonterm_t * adv_{t+1} is a
    composition of affine maps, so it runs as a reverse
    ``lax.associative_scan`` over (coeff, delta) pairs — O(log T) passes
    of vectorised work instead of a T-step sequential dependency chain.
    Matches the sequential scan to float-association accuracy (the
    tests pin it against a hand-rolled backward recursion)."""
    v, r, done = batch["v"], batch["r"], batch["done"]
    nonterm = 1.0 - done
    v_next = jnp.concatenate([v[1:], v_last[None]], axis=0)
    delta = r + gamma * v_next * nonterm - v
    coeff = (gamma * lam) * nonterm

    def compose(a, b):
        # affine map composition — in a reverse associative_scan the
        # SECOND argument is the earlier timestep, which wraps the later
        # suffix: (b ∘ a)(x) = cb*(ca*x + da) + db. Associative, so the
        # scan may regroup freely.
        ca, da = a
        cb, db = b
        return cb * ca, db + cb * da

    _, advs = lax.associative_scan(compose, (coeff, delta), reverse=True)
    returns = advs + v
    return advs, returns


# ---------------------------------------------------------------------------
# PPO update
# ---------------------------------------------------------------------------

def ppo_loss(params, cfg: PPOConfig, mb):
    logits, v = policy_forward(params, mb["x"],
                               fast_gates=cfg.fast_gates)
    logp_all = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(logp_all, mb["a"][..., None], -1)[..., 0]
    ratio = jnp.exp(logp - mb["logp"])
    adv = mb["adv"]
    adv = (adv - adv.mean()) / (adv.std() + 1e-8)
    pg = -jnp.minimum(
        ratio * adv,
        jnp.clip(ratio, 1 - cfg.clip, 1 + cfg.clip) * adv).mean()
    v_loss = jnp.square(v - mb["ret"]).mean()
    ent = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
    total = pg + cfg.value_coef * v_loss - cfg.entropy_coef * ent
    return total, {"pg_loss": pg, "v_loss": v_loss, "entropy": ent}


def make_optimizer(cfg: PPOConfig):
    """The PPO optimizer — one definition shared by the jitted trainer
    and the AOT dry-run lowering (launch/dryrun.py)."""
    return adamw(cfg.lr, weight_decay=0.0, b2=0.999, clip_norm=0.5)


def learner_update_fn(cfg: PPOConfig, opt):
    """The pure learner half of a PPO iteration —
    ``(params, opt_state, batch, v_last, key) -> (params, opt_state,
    metrics)``: GAE + flatten + minibatch epochs over an already-collected
    trajectory batch.

    This is the exact program ``train_iteration`` runs after its rollout
    (the integrated trainer calls it), split out so the *disaggregated*
    actor/learner trainer (``distributed/actor_learner.py``) applies the
    identical update to batches streamed in from rollout workers. PPO's
    clipped ratio ``exp(logp_new - logp_behavior)`` is computed against
    the ``logp`` the batch was *acted* with, so a batch produced by a
    stale policy version is importance-corrected (and clipped) for free —
    that, plus the fleet's ``max_staleness`` drop policy, is the
    off-policy correction story (documented in ARCHITECTURE's
    fault-tolerance contract).

    The flattened samples are packed once per iteration into one f32
    record per sample, ``[a | logp | adv | ret]`` with the action stored
    as its exact f32 value (not a bitcast), preceded by the policy input
    ``x`` and zero-padded to one 128-lane tile when ``x`` fits the tile
    beside the four scalars. Each epoch draws one permutation and gathers
    the record with it, once (a wider ``x`` is gathered on its own beside
    the 4-wide record): a gather pays its cost per index, not per byte
    of the row, so one gather replaces one per field. The minibatches
    ``ppo_loss`` sees are bitwise those of per-field gathers under the
    same permutation, ``a`` still int32."""

    def learner_update(params, opt_state, batch, v_last, key):
        with jax.named_scope("ppo.gae"):
            adv, ret = gae(batch, v_last, cfg.gamma, cfg.lam)
            total = batch["a"].size      # T * n_envs * n_agents samples
            x = batch["x"].reshape(total, -1)
        F = x.shape[1]
        with jax.named_scope("ppo.shuffle"):
            # an exact f32 action, not a bitcast int: that would be a
            # denormal, which a TPU fusion may flush. The zero pad fills
            # the lane tile, else XLA lays a narrow record out
            # samples-minor and each row becomes a strided read. Frames
            # wider than the tile stay out: inside an f32 record they cost
            # the update more relayouts than the saved gather.
            cols = [c.reshape(total, 1).astype(jnp.float32)
                    for c in (batch["a"], batch["logp"], adv, ret)]
            packed = F + len(cols) <= _LANES
            if packed:
                pad = jnp.zeros((total, _LANES - F - len(cols)), jnp.float32)
                rows = [jnp.concatenate([x] + cols + [pad], axis=1)]
            else:
                rows = [x, jnp.concatenate(cols, axis=1)]
        s = F if packed else 0           # first scalar column of a record
        n_mb = cfg.n_minibatches
        mb_size = total // n_mb

        def epoch(carry, k):
            params, opt_state = carry
            # ONE gather of each record per epoch; the scan then streams
            # contiguous (mb_size, width) slices — no per-minibatch
            # gather copies (same minibatch contents as gathering
            # row-by-row)
            with jax.named_scope("ppo.shuffle"):
                perm = jax.random.permutation(k, total)[:n_mb * mb_size]
                shuf = [v.at[perm].get(
                    mode="promise_in_bounds", unique_indices=True,
                    wrap_negative_indices=False).reshape((n_mb, mb_size, -1))
                    for v in rows]

            def mb_step(carry, mb_rows):
                params, opt_state = carry
                r = mb_rows[-1]
                mb = {"x": r[:, :F] if packed else mb_rows[0],
                      "a": r[:, s].astype(jnp.int32),
                      "logp": r[:, s + 1], "adv": r[:, s + 2],
                      "ret": r[:, s + 3]}
                (l, m), g = jax.value_and_grad(ppo_loss, has_aux=True)(
                    params, cfg, mb)
                params, opt_state, _ = opt.update(g, opt_state, params)
                return (params, opt_state), l

            (params, opt_state), ls = lax.scan(mb_step,
                                               (params, opt_state), shuf)
            return (params, opt_state), ls.mean()

        with jax.named_scope("ppo.shuffle"):
            epoch_keys = jax.random.split(key, cfg.epochs)
        # the epoch loop is the update's; its shuffle is scoped inside
        with jax.named_scope("ppo.update"):
            (params, opt_state), losses = lax.scan(
                epoch, (params, opt_state), epoch_keys)
            metrics = {"loss": losses.mean(),
                       "mean_reward": batch["r"].mean(),
                       "mean_value": batch["v"].mean()}
        return params, opt_state, metrics

    return learner_update


def train_iteration_fn(env, cfg: PPOConfig, opt, mesh=None):
    """The pure (un-jitted) one-PPO-iteration function —
    ``(params, opt_state, rs, key) -> (params, opt_state, rs, metrics)``.
    ``make_train_iteration`` jits it with donation; the dry-run harness
    lowers it AOT with explicitly sharded arguments instead. ``mesh``
    pins the rollout state to the IALS partition rules at iteration entry
    (params and optimizer state stay replicated — pure DP, gradients
    all-reduce); ``mesh=None`` adds no constraint ops. The learner half
    is ``learner_update_fn`` — shared verbatim with the disaggregated
    actor/learner trainer, so the two trainers apply bitwise-identical
    updates to identical batches."""
    learner_update = learner_update_fn(cfg, opt)

    def train_iteration(params, opt_state, rs: RolloutState, key):
        if mesh is not None:
            from repro.distributed import sharding as shd
            rs = shd.constrain_ials_state(rs, mesh, cfg.n_agents)
        with jax.named_scope("ppo.noise"):
            k_roll, k_upd = jax.random.split(key)
        rs, batch, v_last = rollout(env, cfg, params, rs, k_roll)
        params, opt_state, metrics = learner_update(
            params, opt_state, batch, v_last, k_upd)
        return params, opt_state, rs, metrics

    return train_iteration


def make_train_iteration(env, cfg: PPOConfig, mesh=None):
    opt = make_optimizer(cfg)
    # donation audit: params / opt_state / rollout state update in place
    # every iteration; the key is tiny and freshly split by the caller,
    # so it stays undonated
    train_iteration = jax.jit(train_iteration_fn(env, cfg, opt, mesh),
                              donate_argnums=(0, 1, 2))
    return opt, train_iteration


# ---------------------------------------------------------------------------
# Greedy evaluation on the batched whole-horizon path
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def _cached_evaluator(env, cfg: PPOConfig, n_episodes: int, ep_len: int):
    benv = as_batched(env)
    whole = benv.step_det is not None and benv.noise_fn is not None
    ash = cfg.agent_shape

    def run(params, key):
        k0, ks = jax.random.split(key)
        state = benv.reset(k0, n_episodes)
        frames = jnp.zeros((n_episodes,) + ash
                           + (cfg.frame_stack, cfg.obs_dim))
        frames = frames.at[..., -1, :].set(benv.observe(state))
        keys = jax.random.split(ks, ep_len)
        xs = (horizon_noise(benv.noise_fn, keys, n_episodes) if whole
              else keys)

        def tick(carry, x):
            state, frames = carry
            logits, _ = policy_forward(params, _stack_obs(frames),
                                       fast_gates=cfg.fast_gates)
            a = jnp.argmax(logits, -1)
            if whole:
                state, obs, r, _ = benv.step_det(state, a, x)
            else:
                state, obs, r, _ = benv.step(state, a, x)
            frames = jnp.concatenate(
                [frames[..., 1:, :], obs[..., None, :]], axis=-2)
            return (state, frames), r

        _, rews = lax.scan(tick, (state, frames), xs, unroll=8)
        return rews.mean(axis=0).mean(axis=0)       # () or (n_agents,)

    return jax.jit(run)


def make_evaluator(env, cfg: PPOConfig, *, n_episodes: int = 8,
                   ep_len: int | None = None):
    """-> cached jitted ``fn(params, key) -> mean rewards`` (scalar array,
    or (n_agents,) on a multi-agent env).

    The greedy policy needs no action noise, so evaluation episodes ride
    the batched env protocol directly: episodes ARE the env batch, env
    randomness is drawn in bulk when the env exposes
    ``noise_fn``/``step_det``, and the whole evaluation is one jitted
    scan-of-batched-ticks instead of a vmap of per-episode scalar keyed
    scans. The evaluator is cached per (env, cfg, sizes), so periodic
    evaluation stops re-tracing every call."""
    return _cached_evaluator(env, cfg, n_episodes,
                             ep_len or cfg.episode_len)


def evaluate(env, cfg: PPOConfig, params, key, *, n_episodes: int = 8,
             ep_len: int | None = None, per_agent: bool = False):
    """Mean per-step reward of the greedy policy on ``env`` (the paper's
    periodic evaluation on the GS). ``env`` may be a scalar ``Env`` or a
    native ``BatchedEnv`` (the fused IALS engines evaluate directly).
    With ``per_agent`` on a multi-agent env, returns the (n_agents,)
    per-agent means instead of the overall mean.

    Estimator note: episodes-as-batch draws env randomness with one key
    per tick (shared across episodes, the batched protocol's derivation)
    instead of the historical per-episode key chains — the same
    distribution over trajectories, not the same key stream; the
    equivalence test pins the two paths together on key-independent
    dynamics."""
    run = make_evaluator(env, cfg, n_episodes=n_episodes, ep_len=ep_len)
    rewards = run(params, key)
    if per_agent and cfg.agent_shape:
        return rewards
    return float(jnp.asarray(rewards).mean())
