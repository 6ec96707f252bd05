"""Rational gate activations — the IALS hot-loop's transcendental diet.

Profiling the fused rollout engine on CPU showed the GRU gate
nonlinearities, not the matmuls, dominating the AIP step (~70% of the
per-timestep cost): ``tanh``/``logistic`` lower to expensive transcendental
expansions, and the AIP evaluates ~``3 * H`` of them per lane per tick.
These rational approximations (the degree-7 Lambert continued fraction for
tanh, sigmoid via the tanh half-angle identity) are mul/add-only, vectorize
on any backend, and run inside Pallas kernel bodies unchanged.

Accuracy: |tanh_err| < 1e-4, |sigmoid_err| < 5e-5 over the whole real
line, and both stay exactly inside [-1, 1] / [0, 1] saturation. They are
used *consistently* — AIP training, the XLA rollout path, the Pallas
kernels, and the ``ref.py`` oracles all share these definitions — so the
simulator rolls out exactly the model that was trained, and
kernel-vs-oracle parity is exact rather than approximate.
"""
from __future__ import annotations

import jax.numpy as jnp

# the rational crosses 1 exactly here; clamping at the crossing makes the
# approximation saturate to exactly +-1 (worst-case |err| ~= 9.6e-5)
_CLAMP = 4.97178686


def fast_tanh(x: jnp.ndarray) -> jnp.ndarray:
    """Degree-7/6 rational tanh (Lambert's continued fraction), clamped."""
    x = jnp.clip(x, -_CLAMP, _CLAMP)
    x2 = x * x
    num = x * (135135.0 + x2 * (17325.0 + x2 * (378.0 + x2)))
    den = 135135.0 + x2 * (62370.0 + x2 * (3150.0 + x2 * 28.0))
    return num / den


def fast_sigmoid(x: jnp.ndarray) -> jnp.ndarray:
    """sigmoid(x) = (tanh(x/2) + 1) / 2 on the rational tanh."""
    return 0.5 * (fast_tanh(0.5 * x) + 1.0)


def uniform_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 counter-based random bits -> f32 uniforms on [0, 1).

    Uses the top 24 bits so every value is exactly representable in f32;
    ``uniform_from_bits(bits) < p`` is an unbiased Bernoulli(p) draw up to
    2^-24 probability quantisation. This is the shared threshold-compare
    convention of the fused AIP step (kernel and oracle alike).

    The cast goes through int32 because Mosaic has no uint32 -> float32
    conversion; the top 24 bits fit a signed int32, so the values are
    unchanged.
    """
    top24 = (bits >> 8).astype(jnp.int32)
    return top24.astype(jnp.float32) * (1.0 / (1 << 24))
