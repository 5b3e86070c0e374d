"""Plain reference of DenseNet (Huang et al., arXiv:1608.06993) as the
configuration states it: a 7x7 stride-2 stem with 3x3 max pool, dense
blocks of norm-relu-conv1x1(4k)-norm-relu-conv3x3(k) layers concatenated
onto their input, transitions of norm-relu-conv1x1-avgpool2 that halve
the channels, and a norm-relu-global-average-pool-dense head.  Group norm
(8 groups) stands where the paper has batch norm.

The units are numbered as the configuration's ``cut_layer`` counts them:
the first ``cut_layer`` units (stem first) form the hospital's segment,
the rest the server's.  Parameters carry the names and shapes the program
under test keeps, so that one set of weights made from the seed feeds
both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chip import numerics as N


def units(m: dict) -> list[tuple[str, str, int, int]]:
    """(name, kind, in channels, out channels) of every unit in order."""
    out = [("stem", "stem", m["in_ch"], m["stem_ch"])]
    ch = m["stem_ch"]
    blocks = m["blocks"]
    for bi, n_layers in enumerate(blocks):
        for li in range(n_layers):
            out.append((f"b{bi}_l{li}", "dense", ch, ch + m["growth"]))
            ch += m["growth"]
        if bi != len(blocks) - 1:
            new = int(ch * m["compression"])
            out.append((f"t{bi}", "transition", ch, new))
            ch = new
    out.append(("head", "head", ch, m["n_classes"]))
    return out


def _gn(c):
    return {"scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


def _conv_w(key, k, cin, cout):
    return {"w": N.normal(key, (k, k, cin, cout), math.sqrt(2.0 / (k * k * cin)))}


def _init_unit(key, kind, cin, cout, m):
    if kind == "stem":
        return {"c": _conv_w(key, 7, cin, cout), "n": _gn(cout)}
    if kind == "dense":
        g = m["growth"]
        k1, k2 = jax.random.split(key)
        return {"n1": _gn(cin), "c1": _conv_w(k1, 1, cin, 4 * g),
                "n2": _gn(4 * g), "c2": _conv_w(k2, 3, 4 * g, g)}
    if kind == "transition":
        return {"n": _gn(cin), "c": _conv_w(key, 1, cin, cout)}
    return {"n": _gn(cin),
            "fc": {"w": N.normal(key, (cin, cout), 1.0 / math.sqrt(cin)),
                   "b": jnp.zeros((cout,), jnp.float32)}}


def init(key, m: dict) -> dict:
    """One hospital's whole model: ``{"front": ..., "middle": ...}``."""
    us = units(m)
    keys = jax.random.split(key, len(us))
    params = {"front": {}, "middle": {}}
    for i, ((name, kind, cin, cout), k) in enumerate(zip(us, keys)):
        seg = "front" if i < m["cut_layer"] else "middle"
        params[seg][name] = _init_unit(k, kind, cin, cout, m)
    return params


def _apply_unit(p, kind, x, prec):
    relu = jax.nn.relu
    if kind == "stem":
        h = N.conv(x, p["c"]["w"], prec, stride=2)
        h = relu(N.group_norm(p["n"], h))
        return N.max_pool(h, 3, 2, "SAME")
    if kind == "dense":
        h = N.conv(relu(N.group_norm(p["n1"], x)), p["c1"]["w"], prec)
        h = N.conv(relu(N.group_norm(p["n2"], h)), p["c2"]["w"], prec)
        return jnp.concatenate([x, h.astype(x.dtype)], axis=-1)
    if kind == "transition":
        h = N.conv(relu(N.group_norm(p["n"], x)), p["c"]["w"], prec)
        return N.avg_pool(h, 2, 2)
    h = relu(N.group_norm(p["n"], x)).mean(axis=(1, 2))
    return N.dense(h, p["fc"]["w"], prec) + p["fc"]["b"].astype(h.dtype)


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
