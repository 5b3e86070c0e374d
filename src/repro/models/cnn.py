"""The paper's model families: DenseNet-121 and U-Net (Xception-style
encoder), expressed as an ordered list of *units* so the cut-layer split of
repro.core.partition applies directly ("first 4 layers at the client" ==
units[0:4]).

Activations crossing a segment boundary may be a pytree: the U-Net client
segment emits (hidden, skip_list) — the skip connections crossing the cut are
exactly why the paper measures enormous U-Net communication (774 GB/epoch).

GroupNorm replaces BatchNorm (batch-stat-free; avoids running-stat
synchronization ambiguity across virtual clients — noted in DESIGN.md; the
method ordering C1-C6 does not depend on the norm flavor).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import layers as L

Unit = tuple[str, Callable, Callable]   # (name, init(key)->(p,a), apply(p,x)->x)


# ---------------------------------------------------------------------------
# generic unit-list CNN with cut-layer segments
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CNNModel:
    name: str
    units: tuple[Unit, ...]
    cut: int                       # units[0:cut] -> client (front)
    nls: bool                      # True: last unit -> client tail
    head_from: str = "logits"      # how loss reads the output

    @property
    def seg_bounds(self):
        n = len(self.units)
        tail_start = n - 1 if self.nls else n
        return (0, self.cut), (self.cut, tail_start), (tail_start, n)

    @property
    def seg_names(self):
        return ("front", "middle", "tail") if self.nls else ("front", "middle")

    def init(self, key):
        params, axes = {}, {}
        bounds = dict(zip(("front", "middle", "tail"), self.seg_bounds))
        keys = jax.random.split(key, len(self.units))
        for seg in self.seg_names:
            lo, hi = bounds[seg]
            p, a = {}, {}
            for i in range(lo, hi):
                nm, init_fn, _ = self.units[i]
                p[nm], a[nm] = init_fn(keys[i])
            params[seg], axes[seg] = p, a
        return params, axes

    def init_params(self, key):
        return self.init(key)[0]

    def apply_segment(self, seg_params, seg: str, x, train=False):
        bounds = dict(zip(("front", "middle", "tail"), self.seg_bounds))
        lo, hi = bounds[seg]
        for i in range(lo, hi):
            nm, _, apply_fn = self.units[i]
            x = apply_fn(seg_params[nm], x)
        return x

    def apply(self, params, x, train=False):
        for seg in self.seg_names:
            x = self.apply_segment(params[seg], seg, x, train)
        return x

    def loss(self, params, batch, train=True):
        logits = self.apply(params, batch["image"], train)
        return bce_loss(logits, batch["label"])

    def predict(self, params, x):
        return jax.nn.sigmoid(self.apply(params, x).astype(jnp.float32))


def bce_loss(logits, labels):
    logits = logits.reshape(-1).astype(jnp.float32)
    labels = labels.reshape(-1).astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * labels
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


# ---------------------------------------------------------------------------
# DenseNet
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DenseNetConfig:
    name: str = "densenet"
    growth: int = 32
    blocks: tuple[int, ...] = (6, 12, 24, 16)     # DenseNet-121
    stem_ch: int = 64
    compression: float = 0.5
    in_ch: int = 1
    n_classes: int = 1
    cut_layer: int = 4           # paper: first 4 layers at the client
    dtype: Any = jnp.float32


def _dense_layer(cfg: DenseNetConfig, in_ch: int):
    """norm-act-conv1x1(4g) + norm-act-conv3x3(g), concat."""
    g = cfg.growth

    def init(key):
        k1, k2 = jax.random.split(key)
        p, a = {}, {}
        p["n1"], a["n1"] = L.groupnorm_init(in_ch, cfg.dtype)
        p["c1"], a["c1"] = L.conv_init(k1, in_ch, 4 * g, 1, dtype=cfg.dtype)
        p["n2"], a["n2"] = L.groupnorm_init(4 * g, cfg.dtype)
        p["c2"], a["c2"] = L.conv_init(k2, 4 * g, g, 3, dtype=cfg.dtype)
        return p, a

    def apply(p, x):
        h = jax.nn.relu(L.groupnorm_apply(p["n1"], x))
        h = L.conv_apply(p["c1"], h)
        h = jax.nn.relu(L.groupnorm_apply(p["n2"], h))
        h = L.conv_apply(p["c2"], h)
        return jnp.concatenate([x, h], axis=-1)

    return init, apply


def _transition(cfg: DenseNetConfig, in_ch: int, out_ch: int):
    def init(key):
        p, a = {}, {}
        p["n"], a["n"] = L.groupnorm_init(in_ch, cfg.dtype)
        p["c"], a["c"] = L.conv_init(key, in_ch, out_ch, 1, dtype=cfg.dtype)
        return p, a

    def apply(p, x):
        h = jax.nn.relu(L.groupnorm_apply(p["n"], x))
        h = L.conv_apply(p["c"], h)
        return L.avg_pool(h, 2, 2)

    return init, apply


def build_densenet(cfg: DenseNetConfig, cut: int | None = None,
                   nls: bool = False) -> CNNModel:
    units: list[Unit] = []

    def stem_init(key):
        p, a = {}, {}
        p["c"], a["c"] = L.conv_init(key, cfg.in_ch, cfg.stem_ch, 7,
                                     dtype=cfg.dtype)
        p["n"], a["n"] = L.groupnorm_init(cfg.stem_ch, cfg.dtype)
        return p, a

    def stem_apply(p, x):
        h = L.conv_apply(p["c"], x, stride=2)
        h = jax.nn.relu(L.groupnorm_apply(p["n"], h))
        return L.max_pool(h, 3, 2, "SAME")

    units.append(("stem", stem_init, stem_apply))
    ch = cfg.stem_ch
    for bi, n_layers in enumerate(cfg.blocks):
        for li in range(n_layers):
            init, apply = _dense_layer(cfg, ch)
            units.append((f"b{bi}_l{li}", init, apply))
            ch += cfg.growth
        if bi != len(cfg.blocks) - 1:
            out = int(ch * cfg.compression)
            init, apply = _transition(cfg, ch, out)
            units.append((f"t{bi}", init, apply))
            ch = out

    final_ch = ch

    def head_init(key):
        p, a = {}, {}
        p["n"], a["n"] = L.groupnorm_init(final_ch, cfg.dtype)
        p["fc"], a["fc"] = L.bias_dense_init(key, final_ch, cfg.n_classes,
                                             axes=("chan", "classes"),
                                             dtype=cfg.dtype)
        return p, a

    def head_apply(p, x):
        h = jax.nn.relu(L.groupnorm_apply(p["n"], x))
        h = L.global_avg_pool(h)
        return L.bias_dense_apply(p["fc"], h)

    units.append(("head", head_init, head_apply))
    return CNNModel(cfg.name, tuple(units),
                    cut=cfg.cut_layer if cut is None else cut, nls=nls)


# ---------------------------------------------------------------------------
# U-Net (depthwise-separable / Xception-flavoured encoder)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class UNetConfig:
    name: str = "unet"
    widths: tuple[int, ...] = (32, 64, 128, 256)   # encoder pyramid
    in_ch: int = 1
    n_classes: int = 1
    cut_layer: int = 2           # paper: first 6 of a deeper net; scaled here
    dtype: Any = jnp.float32
    #: run each separable-conv/norm/ReLU block image by image and
    #: recompute it in the backward pass (``_block``): a unit keeps its
    #: blocks' inputs live, not their internals.  Changes memory and time,
    #: not the mathematics; what lets 768^2 train at batch 4 on one 16 GB
    #: chip.
    remat: bool = False


def _sep_norm_relu(p_c, p_n, x):
    """One separable-conv -> group norm -> ReLU block."""
    return jax.nn.relu(L.groupnorm_apply(p_n, L.sepconv_apply(p_c, x)))


def _up_cat_sep_norm_relu(p_c, p_n, x, skip):
    """A decoder's first block: ``_sep_norm_relu`` of the upsampled input
    concatenated with the skip.  The concatenation is never materialised
    (``sepconv_concat_apply``), and with the upsample inside the block a
    checkpoint keeps the low-resolution input."""
    h = L.sepconv_concat_apply(p_c, (L.upsample2x(x), skip))
    return jax.nn.relu(L.groupnorm_apply(p_n, h))


def _block(cfg: UNetConfig, fn):
    """``fn(p_c, p_n, *xs)`` as the model runs it.  With ``cfg.remat``
    the batch goes through one image at a time, and each image's block
    is recomputed in the backward pass: the images do not interact (group
    norm is per image), and what the backward pass holds beyond the
    blocks' inputs is one image's block, not the batch's.  Checkpointing
    the same blocks over the whole batch is not enough at 768^2 and batch
    4: the SL whole-run program then needs 16.80e9 of a v5e's 16.91e9
    bytes (AOT ``memory_analysis``), one image at a time 14.89e9."""
    if not cfg.remat:
        return fn
    one = jax.checkpoint(
        lambda p_c, p_n, *xs: fn(p_c, p_n, *(x[None] for x in xs))[0])

    def block(p_c, p_n, *xs):
        return jax.lax.map(lambda x1: one(p_c, p_n, *x1), xs)

    return block


def _enc_block(cfg: UNetConfig, in_ch: int, out_ch: int, down: bool):
    def init(key):
        k1, k2 = jax.random.split(key)
        p, a = {}, {}
        p["c1"], a["c1"] = L.sepconv_init(k1, in_ch, out_ch, 3, dtype=cfg.dtype)
        p["n1"], a["n1"] = L.groupnorm_init(out_ch, cfg.dtype)
        p["c2"], a["c2"] = L.sepconv_init(k2, out_ch, out_ch, 3, dtype=cfg.dtype)
        p["n2"], a["n2"] = L.groupnorm_init(out_ch, cfg.dtype)
        return p, a

    block = _block(cfg, _sep_norm_relu)

    def apply(p, state):
        x, skips = state
        h = block(p["c1"], p["n1"], x)
        h = block(p["c2"], p["n2"], h)
        if down:                       # bottleneck (no down) adds no skip
            skips = skips + (h,)
            h = L.max_pool(h, 2, 2)
        return (h, skips)

    return init, apply


def _dec_block(cfg: UNetConfig, in_ch: int, skip_ch: int, out_ch: int):
    def init(key):
        k1, k2 = jax.random.split(key)
        p, a = {}, {}
        p["c1"], a["c1"] = L.sepconv_init(k1, in_ch + skip_ch, out_ch, 3,
                                          dtype=cfg.dtype)
        p["n1"], a["n1"] = L.groupnorm_init(out_ch, cfg.dtype)
        p["c2"], a["c2"] = L.sepconv_init(k2, out_ch, out_ch, 3, dtype=cfg.dtype)
        p["n2"], a["n2"] = L.groupnorm_init(out_ch, cfg.dtype)
        return p, a

    first = _block(cfg, _up_cat_sep_norm_relu)
    block = _block(cfg, _sep_norm_relu)

    def apply(p, state):
        x, skips = state
        h = first(p["c1"], p["n1"], x, skips[-1])
        h = block(p["c2"], p["n2"], h)
        return (h, skips[:-1])

    return init, apply


def build_unet(cfg: UNetConfig, cut: int | None = None,
               nls: bool = False) -> CNNModel:
    """Classification-via-segmentation U-Net (paper §3.2): the seg head's
    logit map is pooled into an image-level probability."""
    units: list[Unit] = []

    def lift_init(key):
        return {}, {}

    def lift_apply(p, x):
        return (x, ()) if not isinstance(x, tuple) else x

    units.append(("lift", lift_init, lift_apply))

    chans = [cfg.in_ch] + list(cfg.widths)
    for i, (ci, co) in enumerate(zip(chans[:-1], chans[1:])):
        down = i != len(cfg.widths) - 1
        init, apply = _enc_block(cfg, ci, co, down)
        units.append((f"enc{i}", init, apply))

    ws = list(cfg.widths)
    dec_in = ws[-1]
    for i in range(len(ws) - 2, -1, -1):
        init, apply = _dec_block(cfg, dec_in, ws[i], ws[i])
        units.append((f"dec{i}", init, apply))
        dec_in = ws[i]

    def head_init(key):
        p, a = {}, {}
        p["c"], a["c"] = L.conv_init(key, dec_in, cfg.n_classes, 1,
                                     dtype=cfg.dtype)
        return p, a

    def head_apply(p, state):
        x, _ = state
        seg = L.conv_apply(p["c"], x)                 # (B,H,W,1) logit map
        # smooth-max pooling -> image-level logit
        return jax.nn.logsumexp(seg.reshape(seg.shape[0], -1), axis=-1,
                                keepdims=True) - math.log(
                                    seg.shape[1] * seg.shape[2])

    units.append(("head", head_init, head_apply))
    return CNNModel(cfg.name, tuple(units),
                    cut=cfg.cut_layer if cut is None else cut, nls=nls)
