"""Public jit'd wrappers around the Pallas kernels.

On a TPU backend the production dispatch compiles the kernel
(``interpret=False``); elsewhere hot-path ops run their ``ref.py`` oracle,
and ``interpret=True`` executes the kernel bodies in Python for
correctness validation against ``ref.py`` (ARCHITECTURE §2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import aip_step as _aip
from . import flash_attention as _fa
from . import gru as _gru
from . import ref as _ref
from . import rmsnorm as _rms


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention_mha(q, k, v, *, causal=True, scale=None, bq=128, bk=128):
    """q: (B, T, H, D); k, v: (B, S, KH, D) with GQA support.

    Flattens (B, H) into the kernel batch; GQA KV heads are repeated into
    query-head groups OUTSIDE the kernel (zero-copy broadcast reshape).
    """
    B, T, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    G = H // KH
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kf = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (B, KH, G, S, D)).reshape(B * H, S, D)
    vf = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (B, KH, G, S, v.shape[-1])).reshape(B * H, S,
                                                              v.shape[-1])
    o = _fa.flash_attention(qf, kf, vf, causal=causal, scale=scale,
                            bq=bq, bk=bk, interpret=_default_interpret())
    return o.reshape(B, H, T, -1).transpose(0, 2, 1, 3)


def gru_sequence(params, xs, h0=None):
    """Drop-in for repro.nn.rnn.gru_sequence backed by the fused kernel."""
    B, T, D = xs.shape
    H = params["wh"].shape[0]
    if h0 is None:
        h0 = jnp.zeros((B, H), xs.dtype)
    return _gru.gru_sequence(xs, params["wx"], params["wh"], params["b"],
                             h0, interpret=_default_interpret())


def aip_step(d, h, wx, wh, b, hw, hb, bits):
    """Fused IALS AIP tick: GRU cell + head + sigmoid + Bernoulli draw.

    On TPU this is one compiled Pallas invocation with the state resident
    in VMEM. Elsewhere it dispatches the pure-jnp oracle directly — the
    same math as the kernel (shared ``repro.nn.act`` gates and
    threshold-compare), but without interpret-mode's per-grid-point
    emulation overhead, because this op sits on the rollout hot path.
    """
    if jax.default_backend() == "tpu":
        return _aip.aip_step(d, h, wx, wh, b, hw, hb, bits,
                             interpret=False)
    return _ref.aip_step_ref(d, h, wx, wh, b, hw, hb, bits)


def aip_step_multi(d, h, wx, wh, b, hw, hb, bits):
    """A per-agent fused AIP ticks with stacked (A, ...) weights.

    d: (B, A, D); h: (B, A, H); bits: (B, A, M) uint32 -> (h_new, logits,
    u), all leading (B, A). On TPU: an agent-axis vmap of the compiled
    ``aip_step`` kernel (one batched invocation). Elsewhere: the
    vmapped-per-agent oracle — numerically equal to the stacked
    ``ref.aip_step_multi_ref`` einsum but measurably faster under XLA CPU
    (see the ``--ab`` bench's stacked-vs-vmapped tick rows), and the
    exact computation the whole-horizon rollout oracle scans, so the
    per-tick and forced-ops routes stay bitwise-equal.
    """
    if jax.default_backend() == "tpu":
        return jax.vmap(
            lambda dd, hh, a1, a2, a3, a4, a5, bt: _aip.aip_step(
                dd, hh, a1, a2, a3, a4, a5, bt, interpret=False),
            in_axes=(1, 1, 0, 0, 0, 0, 0, 1), out_axes=(1, 1, 1))(
                d, h, wx, wh, b, hw, hb, bits)
    return _ref.aip_step_multi_vmapped_ref(d, h, wx, wh, b, hw, hb, bits)


def ials_rollout_multi(ls, h0, wx, wh, b, hw, hb, actions, bits, noise, *,
                       n_agents, tick_fn, dset_fn, block_b=None,
                       interpret=None):
    """Whole-horizon fused IALS rollout, GRU backbone, A per-agent AIPs:
    T coupled AIP+LS ticks for every A·B agent-major lane in ONE kernel
    dispatch (``aip_rollout_multi``'s (A, B-blocks, T) grid, per-agent
    weights indexed by the agent coordinate of each lane block) on TPU;
    the identical-math ``ref.ials_rollout_multi_ref`` scan elsewhere.
    Both paths run the caller's ``tick_fn``/``dset_fn`` on the same
    values in the same order, so they agree bitwise given the same bits
    and noise.

    ``interpret=None`` is the production dispatch above; passing a bool
    forces the Pallas kernel itself (interpret mode off-TPU — the parity
    tests exercise the real grid/scratch machinery that way).
    """
    if interpret is None:
        if jax.default_backend() == "tpu":
            return _aip.aip_rollout_multi(
                tuple(ls), h0, wx, wh, b, hw, hb, actions, bits,
                tuple(noise), n_agents=n_agents, tick_fn=tick_fn,
                dset_fn=dset_fn, block_b=block_b, interpret=False)
        return _ref.ials_rollout_multi_ref(
            tuple(ls), h0, wx, wh, b, hw, hb, actions, bits, tuple(noise),
            n_agents=n_agents, tick_fn=tick_fn, dset_fn=dset_fn)
    return _aip.aip_rollout_multi(
        tuple(ls), h0, wx, wh, b, hw, hb, actions, bits, tuple(noise),
        n_agents=n_agents, tick_fn=tick_fn, dset_fn=dset_fn,
        block_b=block_b, interpret=interpret)


def fnn_rollout(ls, buf0, w1, b1, w2, b2, hw, hb, actions, bits, noise, *,
                n_agents, tick_fn, dset_fn, block_b=None, interpret=None):
    """Whole-horizon fused IALS rollout, FNN backbone (the Theorem-1
    k-step predictor): frame-stack shift + two relu GEMMs + head + draw
    traced into ``fnn_rollout``'s kernel body on TPU, the identical-math
    ``ref.fnn_rollout_ref`` scan elsewhere. Layout and ``interpret``
    semantics as in ``ials_rollout_multi``; ``buf0`` is the
    (L, stack·d_in) flattened frame buffer.
    """
    if interpret is None:
        if jax.default_backend() == "tpu":
            return _aip.fnn_rollout(
                tuple(ls), buf0, w1, b1, w2, b2, hw, hb, actions, bits,
                tuple(noise), n_agents=n_agents, tick_fn=tick_fn,
                dset_fn=dset_fn, block_b=block_b, interpret=False)
        return _ref.fnn_rollout_ref(
            tuple(ls), buf0, w1, b1, w2, b2, hw, hb, actions, bits,
            tuple(noise), n_agents=n_agents, tick_fn=tick_fn,
            dset_fn=dset_fn)
    return _aip.fnn_rollout(
        tuple(ls), buf0, w1, b1, w2, b2, hw, hb, actions, bits,
        tuple(noise), n_agents=n_agents, tick_fn=tick_fn, dset_fn=dset_fn,
        block_b=block_b, interpret=interpret)


def ials_rollout(ls, h0, wx, wh, b, hw, hb, actions, bits, noise, *,
                 tick_fn, dset_fn, block_b=None, interpret=None):
    """Whole-horizon fused IALS rollout: T coupled AIP+LS ticks in ONE
    kernel dispatch, AIP hidden state and LS leaves VMEM-resident across
    the horizon (``aip_rollout``'s (B-blocks, T) grid) on TPU; the
    identical-math ``ref.ials_rollout_ref`` scan elsewhere. Both paths run
    the caller's ``tick_fn``/``dset_fn`` on the same values in the same
    order, so they agree bitwise given the same bits and noise.

    ``interpret=None`` is the production dispatch above; passing a bool
    forces the Pallas kernel itself (interpret mode off-TPU — the parity
    tests exercise the real grid/scratch machinery that way).
    """
    if interpret is None:
        if jax.default_backend() == "tpu":
            return _aip.aip_rollout(tuple(ls), h0, wx, wh, b, hw, hb,
                                    actions, bits, tuple(noise),
                                    tick_fn=tick_fn, dset_fn=dset_fn,
                                    block_b=block_b, interpret=False)
        return _ref.ials_rollout_ref(tuple(ls), h0, wx, wh, b, hw, hb,
                                     actions, bits, tuple(noise),
                                     tick_fn=tick_fn, dset_fn=dset_fn)
    return _aip.aip_rollout(tuple(ls), h0, wx, wh, b, hw, hb, actions,
                            bits, tuple(noise), tick_fn=tick_fn,
                            dset_fn=dset_fn, block_b=block_b,
                            interpret=interpret)


def policy_rollout(ls, s0, frames0, aip_w, pol_w, gumbel, bits, done,
                   noise, reset_ls, *, kind, n_agents, fast_gates,
                   tick_fn, dset_fn, obs_fn, block_b=None,
                   interpret=None):
    """Whole-horizon actor-in-the-loop IALS rollout: an entire PPO acting
    horizon — policy forward on the VMEM-resident frame stack,
    Gumbel-argmax action sampling on pre-drawn noise, the AIP backbone
    cell (``kind`` in {"gru", "fnn"}) with its Bernoulli draw, the LS
    transition + reward, and the periodic episode-reset merge — in ONE
    kernel dispatch (``aip_step.policy_rollout``'s (A, B-blocks, T) grid)
    on TPU; the identical-math ``ref.policy_rollout_ref`` scan elsewhere.
    Both paths run the caller's ``tick_fn``/``dset_fn``/``obs_fn`` on the
    same values in the same order, so they agree bitwise given the same
    streams — and both are bitwise with PPO's own hoisted scan, which is
    what lets the engine hand its acting loop over wholesale.

    ``interpret=None`` is the production dispatch above; passing a bool
    forces the Pallas kernel itself (interpret mode off-TPU — the parity
    tests exercise the real grid/scratch machinery that way).
    """
    if interpret is None:
        if jax.default_backend() == "tpu":
            return _aip.policy_rollout(
                tuple(ls), s0, frames0, tuple(aip_w), tuple(pol_w),
                gumbel, bits, done, tuple(noise), tuple(reset_ls),
                kind=kind, n_agents=n_agents, fast_gates=fast_gates,
                tick_fn=tick_fn, dset_fn=dset_fn, obs_fn=obs_fn,
                block_b=block_b, interpret=False)
        return _ref.policy_rollout_ref(
            tuple(ls), s0, frames0, tuple(aip_w), tuple(pol_w), gumbel,
            bits, done, tuple(noise), tuple(reset_ls), kind=kind,
            n_agents=n_agents, fast_gates=fast_gates, tick_fn=tick_fn,
            dset_fn=dset_fn, obs_fn=obs_fn)
    return _aip.policy_rollout(
        tuple(ls), s0, frames0, tuple(aip_w), tuple(pol_w), gumbel, bits,
        done, tuple(noise), tuple(reset_ls), kind=kind,
        n_agents=n_agents, fast_gates=fast_gates, tick_fn=tick_fn,
        dset_fn=dset_fn, obs_fn=obs_fn, block_b=block_b,
        interpret=interpret)


def serve_forward(frames, mask, pol_w, *, fast_gates, block_s=None,
                  interpret=None):
    """Masked fixed-slot policy forward — the serving tier's one inference
    dispatch (``serving/server.py::PolicyServer`` drives it): the packed
    request slot ``frames`` (S, D) f32 and lane-validity ``mask`` (S,)
    through the PPO actor-critic net (``pol_w`` = the flat
    ``rl/ppo.py::flat_policy_weights`` tuple) -> (logits (S, n_actions),
    v (S,)), pad lanes exactly zeroed INSIDE the dispatch — the kernel
    boundary of the ragged-batch contract (``envs/api.py``): pad-lane
    contents can never perturb a real lane, and at the fixed slot shape
    real-lane outputs are bitwise independent of lane position and pad
    pattern. On TPU this is the compiled Pallas kernel
    (``aip_step.serve_forward``); elsewhere the identical-math oracle
    (``ref.serve_forward_ref``) — both compute the two policy heads as
    one fused GEMM, so logits are bitwise across routes and ``v`` is the
    documented 1-ulp leaf vs the PPO scan forward (ARCHITECTURE §4).

    ``interpret=None`` is the production dispatch above; passing a bool
    forces the Pallas kernel itself (interpret mode off-TPU — the parity
    tests exercise the real grid/block machinery that way).
    """
    if interpret is None:
        if jax.default_backend() == "tpu":
            return _aip.serve_forward(frames, mask, tuple(pol_w),
                                      fast_gates=fast_gates,
                                      block_s=block_s, interpret=False)
        return _ref.serve_forward_ref(tuple(pol_w), frames, mask,
                                      fast_gates=fast_gates)
    return _aip.serve_forward(frames, mask, tuple(pol_w),
                              fast_gates=fast_gates, block_s=block_s,
                              interpret=interpret)


def serve_forward_multi(frames, mask, pidx, pol_ws, *, fast_gates,
                        block_s=None, interpret=None):
    """Cross-policy masked fixed-slot policy forward — the multi-tenant
    serving dispatch (one server, many checkpoints): the packed request
    slot ``frames`` (S, D) f32, lane-validity ``mask`` (S,), and
    per-lane policy indices ``pidx`` (S,) int32 through N stacked
    actor-critic checkpoints (``pol_ws`` = the
    ``rl/ppo.py::stack_policy_weights`` tuple, (N, ...) leading policy
    axis) -> (logits (S, n_actions), v (S,)), pad lanes and unroutable
    ``pidx`` lanes exactly zeroed INSIDE the dispatch. Every lane's
    output is bitwise-identical to the single-policy ``serve_forward``
    of its own checkpoint at the same slot shape — each checkpoint's
    forward runs the exact single-policy cell over the full slot and
    lanes select their row, so cross-policy batching cannot skew a
    tenant's actions (pinned by the N-policies-vs-N-servers parity
    tests). On TPU this is the compiled Pallas kernel
    (``aip_step.serve_forward_multi``); elsewhere the identical-math
    oracle (``ref.serve_forward_multi_ref``).

    ``interpret=None`` is the production dispatch above; passing a bool
    forces the Pallas kernel itself (interpret mode off-TPU — the parity
    tests exercise the real grid/block machinery that way).
    """
    if interpret is None:
        if jax.default_backend() == "tpu":
            return _aip.serve_forward_multi(frames, mask, pidx,
                                            tuple(pol_ws),
                                            fast_gates=fast_gates,
                                            block_s=block_s,
                                            interpret=False)
        return _ref.serve_forward_multi_ref(tuple(pol_ws), frames, mask,
                                            pidx, fast_gates=fast_gates)
    return _aip.serve_forward_multi(frames, mask, pidx, tuple(pol_ws),
                                    fast_gates=fast_gates,
                                    block_s=block_s, interpret=interpret)


def rmsnorm(x, g, *, eps: float = 1e-6):
    shp = x.shape
    out = _rms.rmsnorm(x.reshape(-1, shp[-1]), g, eps=eps,
                       interpret=_default_interpret())
    return out.reshape(shp)
