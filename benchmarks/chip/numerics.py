"""The plain reference's arithmetic, in the precision a comparison asks for.

The reference runs every convolution and matrix product through ``conv``
and ``dense`` here, so that one argument sets the precision of a whole
forward and backward pass:

* ``"highest"`` — float32 products and sums (``lax.Precision.HIGHEST``):
  the reference proper.
* ``"high3"`` — the precision of the three-pass bfloat16 product that
  ``Precision.HIGH`` names on a TPU, spelled out: every operand of every
  product, forward and backward, is rounded to the sum of two bfloat16
  numbers (16 bits of mantissa) and the products are summed in float32.
  Written out so that it computes the same on every backend; the control
  of a float32 configuration at ``highest``.
* ``"default"`` — the backend's default precision (one bfloat16 pass for
  float32 operands on a TPU, float32 on a CPU).
* ``"bf16"`` — bfloat16 operands and activations, float32 accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high3", "default", "bf16")
_LAX = {"highest": jax.lax.Precision.HIGHEST,
        "default": jax.lax.Precision.DEFAULT}


def act_dtype(prec: str):
    return jnp.bfloat16 if prec == "bf16" else jnp.float32


def _two_bf16(v):
    """``v`` rounded to a bfloat16 head plus a bfloat16 tail."""
    v = v.astype(jnp.float32)
    hi = v.astype(jnp.bfloat16).astype(jnp.float32)
    return hi + (v - hi).astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _round(v):
    return _two_bf16(v)


_round.defvjp(lambda v: (_two_bf16(v), None), lambda _, g: (g,))


@jax.custom_vjp
def _round_cotangent(v):
    return v


_round_cotangent.defvjp(lambda v: (v, None), lambda _, g: (_two_bf16(g),))


def _three_pass(op, a, b):
    return _round_cotangent(op(_round(a), _round(b),
                               jax.lax.Precision.HIGHEST))


def conv(x, w, prec: str, stride: int = 1, padding="SAME", groups: int = 1):
    """NHWC x HWIO convolution."""
    def op(a, b, precision=None, out=jnp.float32):
        return jax.lax.conv_general_dilated(
            a, b, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=precision,
            preferred_element_type=out)

    if prec == "high3":
        return _three_pass(op, x, w)
    if prec == "bf16":
        return op(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                  out=jnp.bfloat16)
    return op(x, w, _LAX[prec])


def dense(x, w, prec: str):
    def op(a, b, precision=None, out=jnp.float32):
        return jnp.matmul(a, b, precision=precision,
                          preferred_element_type=out)

    if prec == "high3":
        return _three_pass(op, x, w)
    if prec == "bf16":
        return op(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                  out=jnp.bfloat16)
    return op(x, w, _LAX[prec])


def group_norm(p, x, groups: int = 8, eps: float = 1e-5):
    """Group norm over (H, W, channels of a group), in float32; the result
    takes the activation's dtype."""
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    y = ((xg - mu) / jnp.sqrt(var + eps)).reshape(b, h, w, c)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def max_pool(x, window, stride, padding="VALID"):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, window, window, 1),
                                 (1, stride, stride, 1), padding)


def avg_pool(x, window, stride):
    s = jax.lax.reduce_window(x, 0.0, jax.lax.add,
                              (1, window, window, 1), (1, stride, stride, 1),
                              "VALID")
    return s / (window * window)


@jax.custom_vjp
def int8_link(x):
    """The cut layer's int8 link: per-row (last axis) absmax scale,
    round to nearest, dequantize.  Gradients pass straight through."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                        1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127)
    return (q * scale).astype(x.dtype)


int8_link.defvjp(lambda x: (int8_link(x), None), lambda _, g: (g,))

LINKS = {"identity": lambda x: x, "int8": int8_link}


def bce(logits, labels):
    z = logits.reshape(-1).astype(jnp.float32)
    y = labels.reshape(-1).astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def normal(key, shape, scale):
    return scale * jax.random.normal(key, shape, jnp.float32)
