"""Operations and bytes of the cells' work, computed from shapes.

``forward_flops`` walks the jaxpr of a reference forward pass and counts
every convolution and matrix product at 2 operations per multiply-add:
a convolution ``2 * output elements * (kernel height * width * input
channels / groups)``, a product ``2 * M * N * K``.  Nothing else counts
(norms, activations, pooling and the link are a small share of a CNN's
operations), and no backend's cost model is consulted, so the count is
the same on every machine.  A training step costs three forward passes
(forward, and the two products of the backward pass).

``cut_elements`` counts the elements per image of every array that
crosses the cut layer; the int8 link reads each once in float32 and
writes it once (``CUT_BYTES_PER_ELEMENT``) and does ``CUT_FLOPS_PER_ELEMENT``
elementwise operations on it (absolute value, maximum, divide, round,
clip, multiply).
"""

from __future__ import annotations

import math

import jax
import jax.extend
import jax.numpy as jnp

TRAIN_PASSES = 3
CUT_BYTES_PER_ELEMENT = 8
CUT_FLOPS_PER_ELEMENT = 6


def _count(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "conv_general_dilated":
            lhs, rhs = (v.aval for v in eqn.invars[:2])
            out = eqn.outvars[0].aval
            dn = eqn.params["dimension_numbers"]
            k = math.prod(rhs.shape[d] for d in dn.rhs_spec[2:])
            cin = lhs.shape[dn.lhs_spec[1]] // eqn.params["feature_group_count"]
            total += 2 * math.prod(out.shape) * k * cin
        elif name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval
            kdim = math.prod(lhs.shape[d] for d in lc)
            total += 2 * math.prod(eqn.outvars[0].aval.shape) * kdim
        for sub in _subjaxprs(eqn.params.values()):
            total += _count(sub)
    return total


def _subjaxprs(values):
    for v in values:
        if isinstance(v, jax.extend.core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax.extend.core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            yield from _subjaxprs(v)


def forward_flops(fn, *shapes) -> int:
    """Operations of ``fn`` applied to arrays of ``shapes``
    (``ShapeDtypeStruct`` or arrays); nothing is computed."""
    return _count(jax.make_jaxpr(fn)(*shapes).jaxpr)


def model_flops_per_image(family, model: dict, image_size: int) -> int:
    """Forward operations of one image through the whole model."""
    params = jax.eval_shape(lambda k: family.init(k, model),
                            jax.random.key(0))
    img = jax.ShapeDtypeStruct((1, image_size, image_size, model["in_ch"]),
                               jnp.float32)
    return forward_flops(
        lambda p, x: family.logits(p, x, model, "highest"), params, img)


def cut_elements(family, model: dict, image_size: int) -> int:
    """Elements per image of everything that crosses the cut layer."""
    params = jax.eval_shape(lambda k: family.init(k, model),
                            jax.random.key(0))
    img = jax.ShapeDtypeStruct((1, image_size, image_size, model["in_ch"]),
                               jnp.float32)
    out = jax.eval_shape(
        lambda p, x: family.segment(p["front"], "front", x, model,
                                    "highest"), params, img)
    return sum(math.prod(l.shape) for l in jax.tree.leaves(out))
