"""Matrix products of the plain references at a stated precision.

``"f32"``: float32 operands at ``Precision.HIGHEST`` (on a TPU a float32
product otherwise runs in bfloat16 passes). ``"fp8"``: each operand is
rounded to float8_e4m3fn after a per-tensor scale to its largest
magnitude (448), then multiplied exactly in float32: the precision one
step below the configurations' bfloat16, which is what the control of the
comparison runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def q8(x: jax.Array) -> jax.Array:
    """Per-tensor scaled round trip through float8_e4m3fn, in float32.
    Differentiation sees the identity (straight-through): the products
    take float8 operands, and the cotangents pass in float32."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _prep(x, prec):
    x = x.astype(jnp.float32)
    return q8(x) if prec == "fp8" else x


def einsum(spec: str, a: jax.Array, b: jax.Array, prec: str = "f32") -> jax.Array:
    return jnp.einsum(spec, _prep(a, prec), _prep(b, prec), precision=HIGHEST)


def mm(a: jax.Array, b: jax.Array, prec: str = "f32") -> jax.Array:
    return jnp.matmul(_prep(a, prec), _prep(b, prec), precision=HIGHEST)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean over positions of logsumexp - the label's logit, in float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
