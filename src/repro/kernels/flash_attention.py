"""Flash-attention forward Pallas TPU kernel.

TPU adaptation of the Dao flash algorithm: the (q-block, kv-block) loop is
the Pallas *grid* — (batch*heads, T/bq, S/bk) with the kv axis innermost and
"arbitrary" semantics — while online-softmax state (m, l, acc) lives in VMEM
scratch that persists across the kv-grid steps. Block shapes default to the
MXU-native 128x128; both matmuls (q@k^T and p@v) hit the MXU per tile, and
the softmax rescale is fused in-register. No (T, S) score matrix ever exists.

Validated against ``ref.flash_attention_ref`` in interpret mode (this
container is CPU-only; TPU is the target).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, bq: int, bk: int, nk: int):
    iq = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0].astype(jnp.float32)                  # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk) MXU

    if causal:
        q_idx = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1)
    v = v_ref[0].astype(jnp.float32)                  # (bk, Dv)
    acc_new = acc_prev * alpha[:, None] + \
        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))   # MXU

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-20)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    scale: float | None = None, bq: int = 128, bk: int = 128,
                    interpret: bool = True):
    """q: (BH, T, D); k, v: (BH, S, D[v]). Heads pre-flattened into batch
    (GQA callers repeat or group KV before the kernel)."""
    BH, T, D = q.shape
    S = k.shape[1]
    Dv = v.shape[2]
    scale = (D ** -0.5) if scale is None else scale
    bq = min(bq, T)
    bk = min(bk, S)
    assert T % bq == 0 and S % bk == 0
    nq, nk = T // bq, S // bk

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
