"""Fused AIP Pallas TPU kernels: one tick (``aip_step``) and one whole
horizon (the ``aip_rollout`` family).

The IALS inner loop (Algorithm 2 lines 5-8) is: query the AIP on d_t, turn
the logits into per-head Bernoulli probabilities, and draw u_t. Dispatched
op-by-op that is a backbone forward pass, a head matmul, a sigmoid, a
uniform draw and a compare — five round-trips through HBM for a state that
fits in one VMEM tile. ``aip_step`` fuses the whole thing for the GRU
backbone: both GRU matmuls on the MXU, the gate nonlinearities, the head
projection, the head sigmoid, and the Bernoulli threshold-compare against
caller-supplied counter-based random bits, with every intermediate
resident in VMEM.

The rollout kernels go one level up (the Large-Batch-Simulation move,
Shacklett et al. 2021): ONE generalized grid, ``(A, B-blocks, T)`` —
agents and lane blocks on parallel axes, the horizon on an inner
"arbitrary" axis like ``gru.py`` — with the AIP recurrent state AND the
local simulator's state leaves resident in VMEM scratch across all T grid
steps. Lanes are laid out *agent-major* (lane ``a*B + b``), so every lane
block belongs to exactly one agent and the per-agent weights are just
another blocked input indexed by the grid's agent coordinate; the
agent axis is a grid dimension, not a Python-level engine variant. The
caller supplies the LS transition (``tick_fn``) and d-set extraction
(``dset_fn``) as pure jnp functions that get traced straight into the
kernel body, so one ``pallas_call`` advances the entire coupled AIP+LS
system for the whole horizon: actions, random bits, and LS noise stream
in block-by-tick; only per-tick rewards and the final states ever leave
VMEM.

Two backbones share that one kernel body (``_rollout_kernel``), each as a
cell traced into it:
  - ``aip_rollout_multi`` — GRU cell + head (``_gru_cell``), recurrent
    state = the (lanes, H) hidden vector; ``aip_rollout`` is its A=1
    squeeze (kept as the historical single-agent entry point).
  - ``fnn_rollout`` — the finite-memory FNN of Theorem 1: frame-stack
    shift + two relu GEMMs + head (``_fnn_cell``), recurrent state = the
    (lanes, stack·d_in) flattened d-set buffer.

``policy_rollout`` goes one level further still: the PPO *actor* joins
the loop. Its kernel body (``_policy_rollout_kernel``) traces the policy
network (``_policy_cell`` — the exact ``rl/ppo.py::policy_forward``
math, frame stack in VMEM scratch like ``fnn_rollout``'s d-set buffer),
Gumbel-argmax action sampling on pre-drawn noise (bitwise-equal to
``jax.random.categorical``'s own Gumbel-max derivation), either backbone
cell, the LS transition, the observation function, and the periodic
episode-reset merge into one grid — an entire PPO rollout (act + AIP +
LS + reward) is ONE dispatch on TPU.

Randomness is *passed in* as uint32 bits (one `jax.random.bits` call per
tick, generated in bulk by the rollout engine) so the kernels themselves
are pure functions — the same bits give the same u_t on every backend,
which is what the parity tests pin down against the ``ref.py`` oracles.

GRU weights are laid out (D, 3H)/(H, 3H) gate-major [r|z|n] like
``repro/nn/rnn.py``, stacked with a leading (A,) agent axis for the multi
kernels; activations are the shared rational gates from ``repro.nn.act``
(identical in the oracles), so kernel-vs-oracle agreement is exact up to
matmul association order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.nn.act import fast_sigmoid, fast_tanh, uniform_from_bits


def _shift_in(buf, x):
    """Frame-stack shift: drop the oldest (B, d) frame of the flattened
    (B, stack·d) buffer and append ``x`` as the newest. A one-frame stack
    is ``x`` itself (Mosaic has no zero-width vectors)."""
    if buf.shape[1] == x.shape[1]:
        return x
    return jnp.concatenate([buf[:, x.shape[1]:], x], axis=1)


def _gru_cell(w, h, d, bits, *, H: int):
    """One fused GRU-backbone AIP tick on VMEM-resident values.

    w = (wx (D, 3H), wh (H, 3H), b (3H,), hw (H, M), hb (M,)) values;
    h: (B, H) f32 recurrent state; d: (B, D) f32; bits: (B, M) u32
    -> (h2, logits, u) all f32.
    """
    wx, wh, b, hw, hb = (v.astype(jnp.float32) for v in w)
    gx = jax.lax.dot_general(d, wx, (((1,), (0,)), ((), ()))) + b
    gh = jax.lax.dot_general(h, wh, (((1,), (0,)), ((), ())))
    r = fast_sigmoid(gx[:, :H] + gh[:, :H])
    z = fast_sigmoid(gx[:, H:2 * H] + gh[:, H:2 * H])
    n = fast_tanh(gx[:, 2 * H:] + r * gh[:, 2 * H:])
    h2 = (1.0 - z) * n + z * h
    logits = jax.lax.dot_general(h2, hw, (((1,), (0,)), ((), ()))) + hb
    probs = fast_sigmoid(logits)
    u = (uniform_from_bits(bits) < probs).astype(jnp.float32)
    return h2, logits, u


def _fnn_cell(w, buf, d, bits):
    """One fused FNN-backbone AIP tick (the Theorem-1 k-step predictor).

    w = (w1 (S, K), b1 (K,), w2 (K, K), b2 (K,), hw (K, M), hb (M,));
    buf: (B, S) f32 — the frame-stack buffer, S = stack * d_in, flattened
    row-major so the shift is a plain slice; d: (B, d_in) f32; bits:
    (B, M) u32 -> (buf2, logits, u). ``buf2`` already contains d (the
    newest frame last), matching ``influence.step``'s returned buffer.
    """
    w1, b1, w2, b2, hw, hb = (v.astype(jnp.float32) for v in w)
    buf2 = _shift_in(buf, d)
    h = jax.nn.relu(
        jax.lax.dot_general(buf2, w1, (((1,), (0,)), ((), ()))) + b1)
    h = jax.nn.relu(
        jax.lax.dot_general(h, w2, (((1,), (0,)), ((), ()))) + b2)
    logits = jax.lax.dot_general(h, hw, (((1,), (0,)), ((), ()))) + hb
    probs = fast_sigmoid(logits)
    u = (uniform_from_bits(bits) < probs).astype(jnp.float32)
    return buf2, logits, u


def _policy_cell(w, x, *, fast_gates: bool):
    """The PPO actor-critic forward on VMEM-resident values — the exact
    math of ``rl/ppo.py::policy_forward`` (dense = x @ w + b, hidden tanh
    layers through the shared gates; exact ``jnp.tanh`` when the policy
    was configured that way).

    w = (w1 (S, Hp), b1, w2 (Hp, Hp), b2, piw (Hp, n_act), pib,
    vw (Hp, 1), vb) values; x: (B, S) f32 frame-stacked obs
    -> (logits (B, n_act) f32, value (B,) f32).
    """
    w1, b1, w2, b2, piw, pib, vw, vb = (v.astype(jnp.float32) for v in w)
    act = fast_tanh if fast_gates else jnp.tanh
    h = act(jax.lax.dot_general(x, w1, (((1,), (0,)), ((), ()))) + b1)
    h = act(jax.lax.dot_general(h, w2, (((1,), (0,)), ((), ()))) + b2)
    # both heads as ONE (Hp, n_act+1) GEMM: an (Hp, 1) matvec on its own
    # is a fusion-order wildcard (1-ulp drift between program shapes) AND
    # a dispatch-bound micro-GEMM; fusing pins the reduction order shared
    # with the oracle and feeds the MXU one op instead of two
    hw = jnp.concatenate([piw, vw], axis=1)
    hb = jnp.concatenate([pib, vb], axis=-1)
    out = jax.lax.dot_general(h, hw, (((1,), (0,)), ((), ()))) + hb
    # static slices only: an integer column index lowers to a
    # dynamic_slice, which Mosaic cannot compile
    n_act = piw.shape[1]
    return out[:, :n_act], out[:, n_act:].reshape(out.shape[0])


def _aip_step_kernel(d_ref, h_ref, wx_ref, wh_ref, b_ref, hw_ref, hb_ref,
                     bits_ref, h2_ref, logits_ref, u_ref, *, H: int):
    d = d_ref[...].astype(jnp.float32)                 # (B, D)
    h = h_ref[...].astype(jnp.float32)                 # (B, H)
    w = (wx_ref[...], wh_ref[...], b_ref[...], hw_ref[...], hb_ref[...])
    h2, logits, u = _gru_cell(w, h, d, bits_ref[...], H=H)
    h2_ref[...] = h2.astype(h2_ref.dtype)
    logits_ref[...] = logits.astype(logits_ref.dtype)
    u_ref[...] = u.astype(u_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def aip_step(d, h, wx, wh, b, hw, hb, bits, *, interpret: bool | None = None):
    """d: (B, D); h: (B, H); wx: (D, 3H); wh: (H, 3H); b: (3H,);
    hw: (H, M); hb: (M,); bits: (B, M) uint32
    -> (h_new (B, H), logits (B, M) f32, u (B, M) f32 in {0, 1}).

    ``interpret=None`` auto-detects: compiled on TPU, interpret elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, D = d.shape
    H = wh.shape[0]
    M = hw.shape[1]
    kernel = functools.partial(_aip_step_kernel, H=H)
    h2, logits, u = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec((B, D), lambda: (0, 0)),
            pl.BlockSpec((B, H), lambda: (0, 0)),
            pl.BlockSpec((D, 3 * H), lambda: (0, 0)),
            pl.BlockSpec((H, 3 * H), lambda: (0, 0)),
            pl.BlockSpec((3 * H,), lambda: (0,)),
            pl.BlockSpec((H, M), lambda: (0, 0)),
            pl.BlockSpec((M,), lambda: (0,)),
            pl.BlockSpec((B, M), lambda: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, H), lambda: (0, 0)),
            pl.BlockSpec((B, M), lambda: (0, 0)),
            pl.BlockSpec((B, M), lambda: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H), h.dtype),
            jax.ShapeDtypeStruct((B, M), jnp.float32),
            jax.ShapeDtypeStruct((B, M), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(),
        interpret=interpret,
    )(d, h, wx, wh, b, hw, hb, bits)
    return h2, logits, u


def _serve_forward_kernel(f_ref, m_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                          piw_ref, pib_ref, vw_ref, vb_ref, lg_ref, v_ref,
                          *, fast_gates: bool):
    x = f_ref[...].astype(jnp.float32)                 # (bs, D)
    w = (w1_ref[...], b1_ref[...], w2_ref[...], b2_ref[...],
         piw_ref[...], pib_ref[...], vw_ref[...], vb_ref[...])
    logits, v = _policy_cell(w, x, fast_gates=fast_gates)
    m = m_ref[...]                                     # (bs,) int32
    # expand the int32 mask, then compare: Mosaic cannot reshape bools
    lg_ref[...] = jnp.where(m[:, None] != 0, logits,
                            0.0).astype(lg_ref.dtype)
    v_ref[...] = jnp.where(m != 0, v, 0.0).astype(v_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("fast_gates", "block_s", "interpret"))
def serve_forward(frames, mask, pol_w, *, fast_gates: bool,
                  block_s: int | None = None,
                  interpret: bool | None = None):
    """Masked fixed-slot policy forward — the serving tier's inference
    dispatch (``ref.serve_forward_ref`` is the ground truth).

    frames: (S, D) f32 packed request slot (D = frame_stack * obs_dim);
    mask: (S,) int32/bool lane-validity mask; pol_w: the flat
    ``rl/ppo.py::flat_policy_weights`` tuple -> (logits (S, n_actions)
    f32, v (S,) f32), pad lanes exactly zero.

    One grid pass over slot blocks, the whole policy net (two gated
    GEMMs + the fused two-head GEMM of ``_policy_cell``) VMEM-resident
    per block; the mask is applied INSIDE the kernel — the boundary of
    the ragged-batch contract (``envs/api.py``) — so a pad lane's
    contents can never reach a consumer. The slot shape is static per
    server, so every dispatch reuses one compiled program.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, D = frames.shape
    n_act = pol_w[4].shape[1]
    bs = min(block_s or 256, S)
    while S % bs:
        bs //= 2
    mask = mask.astype(jnp.int32)
    kernel = functools.partial(_serve_forward_kernel,
                               fast_gates=fast_gates)
    w1, b1, w2, b2, piw, pib, vw, vb = pol_w
    Hp = w1.shape[1]
    logits, v = pl.pallas_call(
        kernel,
        grid=(S // bs,),
        in_specs=[
            pl.BlockSpec((bs, D), lambda i: (i, 0)),
            pl.BlockSpec((bs,), lambda i: (i,)),
            pl.BlockSpec((D, Hp), lambda i: (0, 0)),
            pl.BlockSpec((Hp,), lambda i: (0,)),
            pl.BlockSpec((Hp, Hp), lambda i: (0, 0)),
            pl.BlockSpec((Hp,), lambda i: (0,)),
            pl.BlockSpec((Hp, n_act), lambda i: (0, 0)),
            pl.BlockSpec((n_act,), lambda i: (0,)),
            pl.BlockSpec((Hp, 1), lambda i: (0, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((bs, n_act), lambda i: (i, 0)),
            pl.BlockSpec((bs,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, n_act), jnp.float32),
            jax.ShapeDtypeStruct((S,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(),
        interpret=interpret,
    )(frames, mask, w1, b1, w2, b2, piw, pib, vw, vb)
    return logits, v


def _serve_forward_multi_kernel(f_ref, m_ref, p_ref, w1_ref, b1_ref,
                                w2_ref, b2_ref, piw_ref, pib_ref, vw_ref,
                                vb_ref, lg_ref, v_ref, *, fast_gates: bool,
                                n_policies: int):
    x = f_ref[...].astype(jnp.float32)                 # (bs, D)
    pidx = p_ref[...]                                  # (bs,)
    stacked = (w1_ref[...], b1_ref[...], w2_ref[...], b2_ref[...],
               piw_ref[...], pib_ref[...], vw_ref[...], vb_ref[...])
    lg = jnp.zeros((x.shape[0], piw_ref.shape[-1]), jnp.float32)
    v = jnp.zeros((x.shape[0],), jnp.float32)
    # static unroll over the (small) policy axis: each checkpoint's cell
    # runs the exact single-policy ``_policy_cell`` at the exact block
    # shape, lanes then select their own row — the bitwise
    # one-policy-vs-N parity depends on this (no per-lane weight gather)
    for n in range(n_policies):
        lg_n, v_n = _policy_cell(tuple(w[n] for w in stacked), x,
                                 fast_gates=fast_gates)
        lg = jnp.where(pidx[:, None] == n, lg_n, lg)
        v = jnp.where(pidx == n, v_n, v)
    m = m_ref[...]                                     # (bs,) int32
    lg_ref[...] = jnp.where(m[:, None] != 0, lg, 0.0).astype(lg_ref.dtype)
    v_ref[...] = jnp.where(m != 0, v, 0.0).astype(v_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("fast_gates", "block_s", "interpret"))
def serve_forward_multi(frames, mask, pidx, pol_ws, *, fast_gates: bool,
                        block_s: int | None = None,
                        interpret: bool | None = None):
    """Cross-policy masked fixed-slot policy forward — ``serve_forward``
    with a leading policy axis on the weights
    (``ref.serve_forward_multi_ref`` is the ground truth).

    frames: (S, D) f32 packed slot; mask: (S,) lane-validity; pidx: (S,)
    int32 per-lane policy index; pol_ws: the stacked
    ``rl/ppo.py::stack_policy_weights`` tuple ((N, ...) arrays) ->
    (logits (S, n_actions) f32, v (S,) f32), pad lanes and unroutable
    ``pidx`` lanes exactly zero.

    Same grid/blocking as ``serve_forward``; the policy axis is a static
    unroll inside the kernel body (every checkpoint's weights are a
    handful of small matrices, VMEM-resident per block), so one compiled
    program serves N checkpoints in one dispatch.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, D = frames.shape
    N = pol_ws[0].shape[0]
    n_act = pol_ws[4].shape[2]
    bs = min(block_s or 256, S)
    while S % bs:
        bs //= 2
    mask = mask.astype(jnp.int32)
    pidx = pidx.astype(jnp.int32)
    kernel = functools.partial(_serve_forward_multi_kernel,
                               fast_gates=fast_gates, n_policies=N)
    w1, b1, w2, b2, piw, pib, vw, vb = pol_ws
    Hp = w1.shape[2]
    logits, v = pl.pallas_call(
        kernel,
        grid=(S // bs,),
        in_specs=[
            pl.BlockSpec((bs, D), lambda i: (i, 0)),
            pl.BlockSpec((bs,), lambda i: (i,)),
            pl.BlockSpec((bs,), lambda i: (i,)),
            pl.BlockSpec((N, D, Hp), lambda i: (0, 0, 0)),
            pl.BlockSpec((N, Hp), lambda i: (0, 0)),
            pl.BlockSpec((N, Hp, Hp), lambda i: (0, 0, 0)),
            pl.BlockSpec((N, Hp), lambda i: (0, 0)),
            pl.BlockSpec((N, Hp, n_act), lambda i: (0, 0, 0)),
            pl.BlockSpec((N, n_act), lambda i: (0, 0)),
            pl.BlockSpec((N, Hp, 1), lambda i: (0, 0, 0)),
            pl.BlockSpec((N, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bs, n_act), lambda i: (i, 0)),
            pl.BlockSpec((bs,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, n_act), jnp.float32),
            jax.ShapeDtypeStruct((S,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(),
        interpret=interpret,
    )(frames, mask, pidx, w1, b1, w2, b2, piw, pib, vw, vb)
    return logits, v


# ---------------------------------------------------------------------------
# The whole-horizon rollout family: one kernel body, two cells, any A
# ---------------------------------------------------------------------------

# TPU layout of the rollout kernels' operands. Mosaic tiles the last two
# dims of every block by (8, 128) unless the block spans the whole dim, so
# the launchers hand the kernels (A, B, *s) lane arrays and (T, A, B, *s)
# streams: one grid step's block is (block_b, *s) with the agent and time
# dims squeezed, and it spans every feature dim. A lane leaf without
# feature dims gets s = (1,) and is squeezed back to (block_b,) inside the
# body. Stacked per-agent weights become (A, r, c) with the agent dim
# squeezed (an (A, K) bias is (A, 1, K)); shared rank-1 weights become
# (1, K).

def _to_kernel(x, A: int, n_lead: int):
    """(*lead, L, *s) -> (*lead, A, L // A, *s), s = (1,) if empty."""
    lead, L, s = x.shape[:n_lead], x.shape[n_lead], x.shape[n_lead + 1:]
    return x.reshape(lead + (A, L // A) + (s or (1,)))


def _stacked_w(w):
    return w.reshape((w.shape[0], 1) + w.shape[1:]) if w.ndim == 2 else w


def _shared_w(w):
    return w.reshape(1, -1) if w.ndim == 1 else w


def _lane_val(ref, flat: bool):
    """Load one lane block: (block_b, *s), or (block_b,) for a leaf that
    had no feature dims (``flat``)."""
    v = ref[...]
    return v.reshape(v.shape[0]) if flat else v


def _put(ref, val):
    ref[...] = val.reshape(ref.shape).astype(ref.dtype)


def _lane_spec(x, block_b: int):
    """(A, B, *s) lane array -> one t-invariant (block_b, *s) lane block."""
    s = x.shape[2:]
    return pl.BlockSpec((pl.squeezed, block_b) + s,
                        lambda a, bi, t, _n=len(s): (a, bi) + (0,) * _n)


def _stream_spec(x, block_b: int):
    """(T, A, B, *s) stream -> one tick of a (block_b, *s) lane block."""
    s = x.shape[3:]
    return pl.BlockSpec((pl.squeezed, pl.squeezed, block_b) + s,
                        lambda a, bi, t, _n=len(s): (t, a, bi) + (0,) * _n)


def _agent_w_spec(w):
    """(A, r, c) stacked weight -> the (r, c) weight of the block's agent."""
    return pl.BlockSpec((pl.squeezed,) + w.shape[1:],
                        lambda a, bi, t: (a, 0, 0))


def _shared_w_spec(w):
    """Shared weight -> the whole array, grid-invariant."""
    return pl.BlockSpec(w.shape, lambda a, bi, t, _n=w.ndim: (0,) * _n)


def _rollout_kernel(*refs, n_ls: int, n_noise: int, n_w: int, T: int,
                    ls_flat, nz_flat, cell_fn, tick_fn, dset_fn):
    """Grid (A, B-blocks, T): agents and lane blocks parallel, horizon
    inner.

    Ref layout (positional): LS state leaves | AIP state s0 | n_w
    weights of this block's agent | actions, bits | noise leaves || final
    LS leaves, sT, rewards || scratch: AIP state, LS leaves. The AIP
    recurrent state and every LS leaf live in VMEM scratch for the whole
    T axis of a lane block; ``cell_fn`` (the backbone), ``tick_fn``, and
    ``dset_fn`` are traced straight into this body."""
    i = n_ls
    ls0 = refs[:n_ls]
    s0_ref = refs[i]
    w_refs = refs[i + 1:i + 1 + n_w]
    i += 1 + n_w
    a_ref, bits_ref = refs[i], refs[i + 1]
    i += 2
    noise_refs = refs[i:i + n_noise]
    i += n_noise
    ls_out = refs[i:i + n_ls]
    sT_ref, rew_ref = refs[i + n_ls], refs[i + n_ls + 1]
    i += n_ls + 2
    s_scr = refs[i]
    ls_scr = refs[i + 1:i + 1 + n_ls]

    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[...].astype(jnp.float32)
        for dst, src in zip(ls_scr, ls0):
            dst[...] = src[...]

    ls_vals = tuple(_lane_val(r, f) for r, f in zip(ls_scr, ls_flat))
    a = _lane_val(a_ref, True)                         # (Bblk,)
    d = dset_fn(ls_vals, a).astype(jnp.float32)        # (Bblk, Dd)
    w = tuple(r[...] for r in w_refs)                  # this block's agent
    s2, _, u = cell_fn(w, s_scr[...], d, bits_ref[...])
    new_ls, rew = tick_fn(ls_vals, a, u,
                          tuple(_lane_val(r, f)
                                for r, f in zip(noise_refs, nz_flat)))
    s_scr[...] = s2
    for dst, val in zip(ls_scr, new_ls):
        _put(dst, val)
    _put(rew_ref, rew)

    @pl.when(t == T - 1)
    def _finish():
        sT_ref[...] = s_scr[...].astype(sT_ref.dtype)
        for dst, src in zip(ls_out, ls_scr):
            dst[...] = src[...]


def _lane_geometry(L: int, n_agents: int, block_b: int | None):
    if L % n_agents:
        raise ValueError(f"lane count {L} not divisible by "
                         f"n_agents={n_agents}")
    B = L // n_agents
    block_b = B if block_b is None else block_b
    if B % block_b:
        raise ValueError(f"block_b={block_b} must divide per-agent "
                         f"batch {B}")
    return B, block_b


def _launch_rollout(cell_fn, ls, s0, weights, actions, bits, noise, *,
                    n_agents: int, tick_fn, dset_fn,
                    block_b: int | None, interpret: bool):
    """Shared ``pallas_call`` builder for the rollout family.

    ``ls``: tuple of (L, ...) LS leaves, L = A·B lanes agent-major;
    ``s0``: (L, K) AIP recurrent state; ``weights``: tuple of (A, ...)
    stacked per-agent weight leaves; ``actions``: (T, L); ``bits``:
    (T, L, M); ``noise``: tuple of (T, L, ...) leaves.
    -> (final ls leaves, s_T (L, K), rewards (T, L) f32)."""
    L, A, T = s0.shape[0], n_agents, actions.shape[0]
    B, block_b = _lane_geometry(L, A, block_b)
    k_ls = [_to_kernel(x, A, 0) for x in ls]
    k_s0 = _to_kernel(s0, A, 0)
    k_w = [_stacked_w(w) for w in weights]
    k_streams = [_to_kernel(x, A, 1) for x in (actions, bits) + noise]
    rew_shape = (T, A, B, 1)
    kernel = functools.partial(
        _rollout_kernel, n_ls=len(ls), n_noise=len(noise), n_w=len(weights),
        T=T, ls_flat=tuple(x.ndim == 1 for x in ls),
        nz_flat=tuple(x.ndim == 2 for x in noise), cell_fn=cell_fn,
        tick_fn=tick_fn, dset_fn=dset_fn)
    out = pl.pallas_call(
        kernel,
        grid=(A, B // block_b, T),
        in_specs=[_lane_spec(x, block_b) for x in k_ls + [k_s0]]
        + [_agent_w_spec(w) for w in k_w]
        + [_stream_spec(x, block_b) for x in k_streams],
        out_specs=[_lane_spec(x, block_b) for x in k_ls + [k_s0]]
        + [_stream_spec(jax.ShapeDtypeStruct(rew_shape, jnp.float32),
                        block_b)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in k_ls + [k_s0]]
        + [jax.ShapeDtypeStruct(rew_shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_b, k_s0.shape[2]), jnp.float32)]
        + [pltpu.VMEM((block_b,) + x.shape[2:], x.dtype) for x in k_ls],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*k_ls, k_s0, *k_w, *k_streams)
    n = len(ls)
    return (tuple(o.reshape(x.shape) for o, x in zip(out[:n], ls)),
            out[n].reshape(s0.shape), out[n + 1].reshape(T, L))


@functools.partial(jax.jit, static_argnames=("n_agents", "tick_fn",
                                             "dset_fn", "block_b",
                                             "interpret"))
def aip_rollout_multi(ls, h0, wx, wh, b, hw, hb, actions, bits, noise, *,
                      n_agents: int, tick_fn, dset_fn,
                      block_b: int | None = None,
                      interpret: bool | None = None):
    """Whole-horizon fused IALS rollout, GRU backbone, A per-agent AIPs —
    ONE kernel dispatch for T ticks of every lane.

    ``ls``: tuple of LS state leaves, each (L, ...) with L = A·B lanes in
    *agent-major* order (lane ``a*B + b``) and a kernel-safe dtype
    (int32/float32 — the engine encodes bools); ``h0``: (L, H) AIP state;
    stacked weights ``wx`` (A, D, 3H), ``wh`` (A, H, 3H), ``b`` (A, 3H),
    ``hw`` (A, H, M), ``hb`` (A, M); ``actions``: (T, L) int32; ``bits``:
    (T, L, M) uint32; ``noise``: tuple of (T, L, ...) LS noise leaves.
    ``tick_fn(ls_leaves, a, u, noise_leaves) -> (ls_leaves, r)`` and
    ``dset_fn(ls_leaves, a) -> (lanes, Dd)`` must be pure jnp — they are
    traced into the kernel body and run on VMEM-resident values.

    -> (final ls leaves, h_T (L, H), rewards (T, L) f32), bitwise-equal
    to scanning the per-tick fused step (``ref.ials_rollout_multi_ref``).

    ``block_b`` lane-blocks the *per-agent* batch axis B across the
    parallel grid dimension (must divide B; default: one block per
    agent). ``interpret=None`` auto-detects: compiled on TPU, interpret
    elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    H = wh.shape[1]
    cell = functools.partial(_gru_cell, H=H)
    return _launch_rollout(cell, tuple(ls), h0, (wx, wh, b, hw, hb),
                           actions, bits, tuple(noise), n_agents=n_agents,
                           tick_fn=tick_fn, dset_fn=dset_fn,
                           block_b=block_b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_agents", "tick_fn",
                                             "dset_fn", "block_b",
                                             "interpret"))
def fnn_rollout(ls, buf0, w1, b1, w2, b2, hw, hb, actions, bits, noise, *,
                n_agents: int, tick_fn, dset_fn,
                block_b: int | None = None,
                interpret: bool | None = None):
    """Whole-horizon fused IALS rollout, FNN backbone (Theorem-1 k-step
    predictor), A per-agent AIPs — the frame-stack shift, both relu
    GEMMs, the head, and the Bernoulli draw all trace into the kernel.

    Layout as in ``aip_rollout_multi`` except the AIP recurrent state:
    ``buf0`` is the (L, stack·d_in) *flattened* frame-stack buffer
    (row-major over (stack, d_in), newest frame last, so the shift is a
    plain slice-and-concat — identical values to ``influence.step``'s
    (stack, d_in) buffer). Stacked weights ``w1`` (A, stack·d_in, K),
    ``b1`` (A, K), ``w2`` (A, K, K), ``b2`` (A, K), ``hw`` (A, K, M),
    ``hb`` (A, M).

    -> (final ls leaves, buf_T (L, stack·d_in), rewards (T, L) f32),
    bitwise-equal to scanning the fused per-tick step
    (``ref.fnn_rollout_ref``).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _launch_rollout(_fnn_cell, tuple(ls), buf0,
                           (w1, b1, w2, b2, hw, hb), actions, bits,
                           tuple(noise), n_agents=n_agents,
                           tick_fn=tick_fn, dset_fn=dset_fn,
                           block_b=block_b, interpret=interpret)


def aip_rollout(ls, h0, wx, wh, b, hw, hb, actions, bits, noise, *,
                tick_fn, dset_fn, block_b: int | None = None,
                interpret: bool | None = None):
    """Single-agent whole-horizon GRU rollout — the A=1 squeeze of
    ``aip_rollout_multi`` (shared-weight lane blocks; kept as the
    historical entry point). Unstacked weights as in ``aip_step``;
    otherwise see ``aip_rollout_multi``.
    """
    return aip_rollout_multi(
        tuple(ls), h0, wx[None], wh[None], b[None], hw[None], hb[None],
        actions, bits, tuple(noise), n_agents=1, tick_fn=tick_fn,
        dset_fn=dset_fn, block_b=block_b, interpret=interpret)


# ---------------------------------------------------------------------------
# Actor-in-the-loop rollout: the policy traced into the same grid
# ---------------------------------------------------------------------------

def _policy_rollout_kernel(*refs, n_ls: int, n_noise: int, n_w: int,
                           T: int, ls_flat, nz_flat, cell_fn, pol_fn,
                           tick_fn, dset_fn, obs_fn):
    """Grid (A, B-blocks, T): one PPO acting tick per grid step.

    Ref layout (positional): LS leaves | AIP state s0 | policy frame
    stack f0 | n_w weights of this block's agent | 8 shared policy
    weights | gumbel, bits, done streams | noise leaves | reset LS leaves
    || final LS leaves, sT, framesT, x, a, logits, v, rewards || scratch:
    AIP state, frames, LS leaves. Per tick: policy forward on the VMEM
    frame stack -> Gumbel-argmax action -> AIP cell + Bernoulli draw -> LS
    transition -> observation refills the frame stack -> the streamed
    ``done`` schedule merges in the streamed reset state (AIP state back
    to zeros, frames re-seeded from the reset observation). Only the PPO
    batch streams and final states leave VMEM."""
    i = n_ls
    ls0 = refs[:n_ls]
    s0_ref, f0_ref = refs[i], refs[i + 1]
    i += 2
    w_refs = refs[i:i + n_w]
    i += n_w
    pw_refs = refs[i:i + 8]
    i += 8
    gum_ref, bits_ref, done_ref = refs[i], refs[i + 1], refs[i + 2]
    i += 3
    noise_refs = refs[i:i + n_noise]
    i += n_noise
    reset_refs = refs[i:i + n_ls]
    i += n_ls
    ls_out = refs[i:i + n_ls]
    i += n_ls
    sT_ref, fT_ref = refs[i], refs[i + 1]
    i += 2
    x_ref, a_ref, lg_ref, v_ref, rew_ref = refs[i:i + 5]
    i += 5
    s_scr, f_scr = refs[i], refs[i + 1]
    ls_scr = refs[i + 2:i + 2 + n_ls]

    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[...].astype(jnp.float32)
        f_scr[...] = f0_ref[...].astype(jnp.float32)
        for dst, src in zip(ls_scr, ls0):
            dst[...] = src[...]

    x = f_scr[...]                                     # (Bblk, S)
    logits, value = pol_fn(tuple(r[...] for r in pw_refs), x)
    a = jnp.argmax(logits + gum_ref[...], axis=-1).astype(jnp.int32)

    ls_vals = tuple(_lane_val(r, f) for r, f in zip(ls_scr, ls_flat))
    d = dset_fn(ls_vals, a).astype(jnp.float32)        # (Bblk, Dd)
    w = tuple(r[...] for r in w_refs)                  # this block's agent
    s2, _, u = cell_fn(w, s_scr[...], d, bits_ref[...])
    new_ls, rew = tick_fn(ls_vals, a, u,
                          tuple(_lane_val(r, f)
                                for r, f in zip(noise_refs, nz_flat)))
    obs = obs_fn(new_ls).astype(jnp.float32)           # (Bblk, d_obs)
    frames2 = _shift_in(x, obs)

    done = _lane_val(done_ref, True)                   # (Bblk,) int32

    def at_done(v):        # reshape the int32 flag, not a bool vector
        return done.reshape((-1,) + (1,) * (v.ndim - 1)) != 0

    ls_m = tuple(jnp.where(at_done(n), _lane_val(r, f), n)
                 for n, r, f in zip(new_ls, reset_refs, ls_flat))
    s_m = jnp.where(at_done(s2), jnp.zeros_like(s2), s2)
    obs0 = obs_fn(ls_m).astype(jnp.float32)
    f_m = jnp.where(at_done(x), _shift_in(jnp.zeros_like(x), obs0), frames2)

    s_scr[...] = s_m
    f_scr[...] = f_m
    for dst, val in zip(ls_scr, ls_m):
        _put(dst, val)
    for ref, val in zip((x_ref, a_ref, lg_ref, v_ref, rew_ref),
                        (x, a, logits, value, rew)):
        _put(ref, val)

    @pl.when(t == T - 1)
    def _finish():
        sT_ref[...] = s_scr[...].astype(sT_ref.dtype)
        fT_ref[...] = f_scr[...].astype(fT_ref.dtype)
        for dst, src in zip(ls_out, ls_scr):
            dst[...] = src[...]


def _launch_policy_rollout(cell_fn, pol_fn, ls, s0, frames0, weights,
                           pol_w, gumbel, bits, done, noise, reset_ls, *,
                           n_agents: int, tick_fn, dset_fn, obs_fn,
                           block_b: int | None, interpret: bool):
    """``pallas_call`` builder for the actor-in-the-loop rollout.

    Layout as in ``_launch_rollout`` plus: ``frames0`` (L, stack·obs_dim)
    f32 policy frame stack; ``pol_w`` tuple of 8 SHARED policy weights
    (full blocks — parameter-shared PPO has no agent axis); ``gumbel``
    (T, L, n_actions) f32; ``done`` (T, L) int32 reset schedule;
    ``reset_ls`` tuple of (T, L, ...) streamed reset-state leaves (same
    dtypes as ``ls``). -> (final ls leaves, s_T, frames_T, x (T, L, S),
    a (T, L) int32, logits (T, L, n_actions), v (T, L), r (T, L))."""
    L, A, T = s0.shape[0], n_agents, gumbel.shape[0]
    B, block_b = _lane_geometry(L, A, block_b)
    S = frames0.shape[1]
    n_act = gumbel.shape[-1]

    k_ls = [_to_kernel(x, A, 0) for x in ls]
    k_state = [_to_kernel(x, A, 0) for x in (s0, frames0)]
    k_w = [_stacked_w(w) for w in weights]
    k_pw = [_shared_w(w) for w in pol_w]
    k_streams = [_to_kernel(x, A, 1)
                 for x in (gumbel, bits, done) + noise + reset_ls]
    stream_outs = [
        jax.ShapeDtypeStruct((T, A, B, S), jnp.float32),       # x
        jax.ShapeDtypeStruct((T, A, B, 1), jnp.int32),         # a
        jax.ShapeDtypeStruct((T, A, B, n_act), jnp.float32),   # logits
        jax.ShapeDtypeStruct((T, A, B, 1), jnp.float32),       # v
        jax.ShapeDtypeStruct((T, A, B, 1), jnp.float32),       # rewards
    ]
    kernel = functools.partial(
        _policy_rollout_kernel, n_ls=len(ls), n_noise=len(noise),
        n_w=len(weights), T=T, ls_flat=tuple(x.ndim == 1 for x in ls),
        nz_flat=tuple(x.ndim == 2 for x in noise), cell_fn=cell_fn,
        pol_fn=pol_fn, tick_fn=tick_fn, dset_fn=dset_fn, obs_fn=obs_fn)
    lane_arrays = k_ls + k_state
    out = pl.pallas_call(
        kernel,
        grid=(A, B // block_b, T),
        in_specs=[_lane_spec(x, block_b) for x in lane_arrays]
        + [_agent_w_spec(w) for w in k_w]
        + [_shared_w_spec(w) for w in k_pw]
        + [_stream_spec(x, block_b) for x in k_streams],
        out_specs=[_lane_spec(x, block_b) for x in lane_arrays]
        + [_stream_spec(o, block_b) for o in stream_outs],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in lane_arrays] + stream_outs,
        scratch_shapes=[pltpu.VMEM((block_b, x.shape[2]), jnp.float32)
                        for x in k_state]
        + [pltpu.VMEM((block_b,) + x.shape[2:], x.dtype) for x in k_ls],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*lane_arrays, *k_w, *k_pw, *k_streams)
    nl = len(ls)
    x, a, lg, v, r = out[nl + 2:]
    return (tuple(o.reshape(l.shape) for o, l in zip(out[:nl], ls)),
            out[nl].reshape(s0.shape), out[nl + 1].reshape(frames0.shape),
            x.reshape(T, L, S), a.reshape(T, L), lg.reshape(T, L, n_act),
            v.reshape(T, L), r.reshape(T, L))


@functools.partial(jax.jit, static_argnames=("kind", "n_agents",
                                             "fast_gates", "tick_fn",
                                             "dset_fn", "obs_fn",
                                             "block_b", "interpret"))
def policy_rollout(ls, s0, frames0, aip_w, pol_w, gumbel, bits, done,
                   noise, reset_ls, *, kind: str, n_agents: int,
                   fast_gates: bool, tick_fn, dset_fn, obs_fn,
                   block_b: int | None = None,
                   interpret: bool | None = None):
    """Whole-horizon actor-in-the-loop IALS rollout — an ENTIRE PPO
    acting horizon (policy forward + Gumbel-argmax action + AIP sample +
    LS transition + reward + periodic episode resets) in ONE kernel
    dispatch, with the policy frame stack, AIP recurrent state, and every
    LS leaf VMEM-resident across all T grid steps.

    ``kind`` picks the AIP backbone cell ("gru": ``aip_w`` = stacked
    (wx, wh, b, hw, hb); "fnn": (w1, b1, w2, b2, hw, hb)); ``pol_w`` is
    the shared policy tuple (w1, b1, w2, b2, piw, pib, vw, vb) evaluated
    with the rational gates when ``fast_gates`` (exact tanh otherwise);
    randomness is all pre-drawn (``gumbel`` for actions, ``bits`` for
    the AIP Bernoulli draw, ``noise`` for the LS, ``reset_ls`` +
    ``done`` for the episode-reset schedule), so the kernel is a pure
    function. ``obs_fn(ls_leaves) -> (lanes, obs_dim)`` must be pure,
    constant-free jnp (the ``BatchedLocalEnv.obs_fn`` contract) — it is
    traced into the body to refill the frame stack each tick.

    Layout and the remaining arguments as in ``aip_rollout_multi`` /
    ``_launch_policy_rollout``; bitwise-equal to
    ``ref.policy_rollout_ref`` given the same streams.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if kind == "gru":
        cell = functools.partial(_gru_cell, H=aip_w[1].shape[1])
    else:
        cell = _fnn_cell
    pol = functools.partial(_policy_cell, fast_gates=fast_gates)
    return _launch_policy_rollout(
        cell, pol, tuple(ls), s0, frames0, tuple(aip_w), tuple(pol_w),
        gumbel, bits, done, tuple(noise), tuple(reset_ls),
        n_agents=n_agents, tick_fn=tick_fn, dset_fn=dset_fn,
        obs_fn=obs_fn, block_b=block_b, interpret=interpret)

