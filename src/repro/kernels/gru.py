"""Fused GRU sequence Pallas TPU kernel — the AIP's hot loop.

The paper's IALS inner loop alternates tiny env steps with a GRU cell
(Algorithm 2 line 7); on GPU this is a cuDNN RNN, on TPU we fuse the whole
cell — both matmuls (x@Wx on the MXU, h@Wh on the MXU) plus all three gate
nonlinearities — into one kernel invocation per timestep, with the hidden
state resident in VMEM scratch across the T-step grid ("arbitrary"
semantics), so h never round-trips to HBM during a rollout.

Weights are laid out (D, 3H)/(H, 3H) gate-major [r|z|n], matching
``repro/nn/rnn.py``; ``ref.gru_sequence_ref`` is the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.nn.act import fast_sigmoid, fast_tanh


def _gru_kernel(x_ref, wx_ref, wh_ref, b_ref, h0_ref, hs_ref, h_scr, *,
                H: int, T: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    x = x_ref[:, 0, :].astype(jnp.float32)            # (B, D)
    h = h_scr[...]                                     # (B, H)
    gx = jax.lax.dot_general(x, wx_ref[...].astype(jnp.float32),
                             (((1,), (0,)), ((), ()))) + \
        b_ref[...].astype(jnp.float32)
    gh = jax.lax.dot_general(h, wh_ref[...].astype(jnp.float32),
                             (((1,), (0,)), ((), ())))
    r = fast_sigmoid(gx[:, :H] + gh[:, :H])
    z = fast_sigmoid(gx[:, H:2 * H] + gh[:, H:2 * H])
    n = fast_tanh(gx[:, 2 * H:] + r * gh[:, 2 * H:])
    h_new = (1.0 - z) * n + z * h
    h_scr[...] = h_new
    hs_ref[:, 0, :] = h_new.astype(hs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gru_sequence(x, wx, wh, b, h0, *, interpret: bool | None = None):
    """x: (B, T, D); wx: (D, 3H); wh: (H, 3H); b: (3H,); h0: (B, H)
    -> (hs (B, T, H), h_T).

    ``interpret=None`` auto-detects the backend: compiled on TPU,
    interpret mode everywhere else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, D = x.shape
    H = wh.shape[0]
    kernel = functools.partial(_gru_kernel, H=H, T=T)
    hs = pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=[
            pl.BlockSpec((B, 1, D), lambda t: (0, t, 0)),
            pl.BlockSpec((D, 3 * H), lambda t: (0, 0)),
            pl.BlockSpec((H, 3 * H), lambda t: (0, 0)),
            pl.BlockSpec((3 * H,), lambda t: (0,)),
            pl.BlockSpec((B, H), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B, 1, H), lambda t: (0, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, H), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x, wx, wh, b, h0)
    return hs, hs[:, -1, :]
