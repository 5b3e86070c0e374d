"""Plain reference of the U-Net the configuration states (Ronneberger et
al., arXiv:1505.04597, with an encoder of depthwise-separable convolutions
after Chollet, arXiv:1610.02357): per encoder width, two blocks of 3x3
depthwise, 1x1 pointwise, group norm and ReLU, a 2x2 max pool after every
unit but the last (its input before the pool is the unit's skip); per
decoder unit, a nearest 2x upsample concatenated with the matching skip
and two such blocks; a 1x1 convolution to a logit map pooled into one
image logit by smooth max (log-sum-exp over the pixels, minus the log of
their count).  Group norm (8 groups) stands where the paper has batch
norm.  No recomputation: the program's ``remat`` changes memory, not the
function.

The units are numbered as the configuration's ``cut_layer`` counts them:
``lift`` (no parameters), ``enc0``..., ``dec...``, ``head``; the first
``cut_layer`` form the hospital's segment.  Everything that crosses the
cut, the encoder's output and its skips, goes through the link.
Parameters carry the names and shapes the program under test keeps, so
that one set of weights made from the seed feeds both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chip import numerics as N


def units(m: dict) -> list[tuple[str, str, int, int]]:
    """(name, kind, in channels, out channels) of every unit in order;
    a decoder's in channels are its input's plus its skip's."""
    ws = list(m["widths"])
    out = [("lift", "lift", m["in_ch"], m["in_ch"])]
    for i, (ci, co) in enumerate(zip([m["in_ch"]] + ws[:-1], ws)):
        kind = "enc" if i < len(ws) - 1 else "bottom"
        out.append((f"enc{i}", kind, ci, co))
    for i in range(len(ws) - 2, -1, -1):
        out.append((f"dec{i}", "dec", ws[i + 1] + ws[i], ws[i]))
    out.append(("head", "head", ws[0], m["n_classes"]))
    return out


def _gn(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _sep(key, cin, cout):
    kd, kp = jax.random.split(key)
    return {"dw": N.normal(kd, (3, 3, 1, cin), math.sqrt(2.0 / 9)),
            "pw": N.normal(kp, (1, 1, cin, cout), math.sqrt(2.0 / cin))}


def _init_unit(key, kind, cin, cout):
    if kind == "lift":
        return {}
    if kind == "head":
        return {"c": {"w": N.normal(key, (1, 1, cin, cout),
                                    math.sqrt(2.0 / cin))}}
    k1, k2 = jax.random.split(key)
    return {"c1": _sep(k1, cin, cout), "n1": _gn(cout),
            "c2": _sep(k2, cout, cout), "n2": _gn(cout)}


def init(key, m: dict) -> dict:
    """One hospital's whole model: ``{"front": ..., "middle": ...}``."""
    us = units(m)
    keys = jax.random.split(key, len(us))
    params = {"front": {}, "middle": {}}
    for i, ((name, kind, cin, cout), k) in enumerate(zip(us, keys)):
        seg = "front" if i < m["cut_layer"] else "middle"
        params[seg][name] = _init_unit(k, kind, cin, cout)
    return params


def _block(pc, pn, x, prec):
    """Separable convolution, group norm, ReLU."""
    h = N.conv(x, pc["dw"], prec, groups=x.shape[-1])
    h = N.conv(h, pc["pw"], prec)
    return jax.nn.relu(N.group_norm(pn, h))


def _upsample(x):
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def _apply_unit(p, kind, state, prec):
    """``state`` is the image before ``lift``, then ``(x, skips)``."""
    if kind == "lift":
        return (state, ())
    x, skips = state
    if kind == "head":
        z = N.conv(x, p["c"]["w"], prec)
        z = z.reshape(z.shape[0], -1)
        return (jax.nn.logsumexp(z, axis=-1, keepdims=True)
                - math.log(z.shape[1]))
    if kind == "dec":
        x = jnp.concatenate([_upsample(x), skips[-1]], axis=-1)
        skips = skips[:-1]
    h = _block(p["c1"], p["n1"], x, prec)
    h = _block(p["c2"], p["n2"], h, prec)
    if kind == "enc":
        return (N.max_pool(h, 2, 2), skips + (h,))
    return (h, skips)


def segment(params_seg: dict, seg: str, x, m: dict, prec: str):
    for i, (name, kind, _, _) in enumerate(units(m)):
        if (i < m["cut_layer"]) == (seg == "front"):
            x = _apply_unit(params_seg[name], kind, x, prec)
    return x


def logits(params: dict, images, m: dict, prec: str, link="identity"):
    """Image batch (B, H, W, C) -> (B, n_classes) logits, with the cut
    layer's ``link`` applied to every array that crosses it."""
    x = images.astype(N.act_dtype(prec))
    x = segment(params["front"], "front", x, m, prec)
    x = jax.tree.map(N.LINKS[link], x)
    return segment(params["middle"], "middle", x, m, prec)
