"""Small traced helpers shared by the parts of a model whose device time
is read by named scope (models/solar_open2.py).

A device trace names each op by the innermost frame of user code it was
traced from. A product made through a helper would carry the helper's
line, whichever part called it; this file is therefore registered with
JAX as not being user code (as flax registers its own), so an op made here
carries the line of the CALLER: the mixer or expert layer it belongs to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend import source_info_util

source_info_util.register_exclusion(__file__)

HIGHEST = jax.lax.Precision.HIGHEST


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps
    ) * scale.astype(jnp.float32)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def mm(x, w, dtype):
    """``x @ w`` multiplied in ``dtype``, accumulated and returned f32."""
    precision = HIGHEST if dtype == jnp.float32 else None
    return jnp.dot(x.astype(dtype), w.astype(dtype), precision=precision,
                   preferred_element_type=jnp.float32)


def ein(spec, a, b, dtype):
    precision = HIGHEST if dtype == jnp.float32 else None
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      precision=precision,
                      preferred_element_type=jnp.float32)


def causal_conv(x, w):
    taps = w.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(padded[:, j:j + x.shape[1]] * w[j] for j in range(taps))
