"""Neural-network primitives shared by every model family.

Everything is pure-functional: ``*_init`` builds a param pytree (dict of
arrays) plus a parallel *logical-axis* tree used by the launcher to derive
PartitionSpecs, and ``*_apply`` consumes it.  No flax/haiku — the cut-layer
partitioning of the paper (see ``repro.core.partition``) needs full control
over the param tree boundaries.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = Any  # pytree of jnp arrays
Axes = Any    # pytree (same structure) of tuples of logical axis names


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def dense_init(key, in_dim: int, out_dim: int, *, axes=("in", "out"),
               dtype=jnp.float32, scale: float | None = None):
    """Weight-only dense layer (bias-free, llama-style)."""
    scale = (1.0 / math.sqrt(in_dim)) if scale is None else scale
    return {"w": _normal(key, (in_dim, out_dim), scale, dtype)}, {"w": axes}


def dense_apply(p, x):
    return x @ p["w"].astype(x.dtype)


def bias_dense_init(key, in_dim, out_dim, *, axes=("in", "out"), dtype=jnp.float32):
    kw, _ = jax.random.split(key)
    w, wa = dense_init(kw, in_dim, out_dim, axes=axes, dtype=dtype)
    w["b"] = jnp.zeros((out_dim,), dtype)
    wa["b"] = (axes[-1],)
    return w, wa


def bias_dense_apply(p, x):
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(dim, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}, {"scale": ("embed",)}


def rmsnorm_apply(p, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(dim, dtype=jnp.float32):
    return ({"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)},
            {"scale": ("embed",), "bias": ("embed",)})


def layernorm_apply(p, x, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dt)


def groupnorm_init(channels, dtype=jnp.float32):
    return ({"scale": jnp.ones((channels,), dtype), "bias": jnp.zeros((channels,), dtype)},
            {"scale": ("chan",), "bias": ("chan",)})


def groupnorm_apply(p, x, groups=8, eps=1e-5):
    """x: (B, H, W, C) — NHWC. Group norm (batch-stat free; see DESIGN.md)."""
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    dt = x.dtype
    xg = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean((xg - mu) ** 2, axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    y = xg.reshape(b, h, w, c) * p["scale"] + p["bias"]
    return y.astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, causal, optional sliding window, KV-cache decode)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    sliding_window: int | None = None   # None => full causal
    chunk_kv: int = 0                   # >0 => chunked (flash-style) jnp prefill


def attention_init(key, cfg: AttnConfig, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p, a = {}, {}
    p["wq"], a["wq"] = _normal(kq, (d, h * hd), 1 / math.sqrt(d), dtype), ("embed", "heads_flat")
    p["wk"], a["wk"] = _normal(kk, (d, kvh * hd), 1 / math.sqrt(d), dtype), ("embed", "kv_flat")
    p["wv"], a["wv"] = _normal(kv, (d, kvh * hd), 1 / math.sqrt(d), dtype), ("embed", "kv_flat")
    p["wo"], a["wo"] = _normal(ko, (h * hd, d), 1 / math.sqrt(h * hd), dtype), ("heads_flat", "embed")
    return p, a


def _full_causal_attn(q, k, v, positions, kv_positions, sliding_window):
    """q: (B,S,H,hd)  k,v: (B,T,KV,hd).  Returns (B,S,H,hd)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    qg = q.reshape(b, s, kvh, rep, hd)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bsgrd,btgd->bgrst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = kv_positions[:, None, :] <= positions[:, :, None]      # (B,S,T)
    if sliding_window is not None:
        mask &= kv_positions[:, None, :] > positions[:, :, None] - sliding_window
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", w, v.astype(jnp.float32))
    return out.reshape(b, s, h, hd).astype(q.dtype)


def _chunked_attn(q, k, v, positions, kv_positions, sliding_window, chunk):
    """Flash-style online-softmax over KV chunks (pure jnp; Pallas kernel is
    the TPU-target twin, see repro.kernels.flash_attention)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    t = k.shape[1]
    n_chunks = (t + chunk - 1) // chunk
    pad = n_chunks * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)),
                               constant_values=jnp.iinfo(jnp.int32).max)
    kc = k.reshape(b, n_chunks, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    qg = q.reshape(b, s, kvh, rep, hd).astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)

    def body(carry, ch):
        m, l, acc = carry
        kb, vb, pb = ch
        logits = jnp.einsum("bsgrd,btgd->bgrst", qg, kb.astype(jnp.float32)) * scale
        mask = pb[:, None, :] <= positions[:, :, None]
        if sliding_window is not None:
            mask &= pb[:, None, :] > positions[:, :, None] - sliding_window
        logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrst,btgd->bgrsd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, rep, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, s), jnp.float32)
    a0 = jnp.zeros((b, kvh, rep, s, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
    return out.astype(q.dtype)


def attention_apply(p, cfg: AttnConfig, x, positions, cache=None,
                    use_pallas: bool = False):
    """x: (B, S, D).  ``cache``: None for train/prefill-without-cache, or
    {"k": (B,T,KV,hd), "v": ..., "pos": (B,T) int32, "index": int} for decode.
    Returns (out, new_cache)."""
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, h, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(b, s, kvh, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(b, s, kvh, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is not None:
        cl = cache["k"].shape[1]
        if s >= cl:
            # bulk prefill larger than a sliding-window ring cache: keep the
            # last `cl` tokens (their natural ring slots when cl | s) and
            # attend over the in-flight keys directly
            ck = k[:, -cl:].astype(cache["k"].dtype)
            cv = v[:, -cl:].astype(cache["v"].dtype)
            cpos = positions[:, -cl:].astype(jnp.int32)
            new_cache = {"k": ck, "v": cv, "pos": cpos,
                         "index": cache["index"] + s}
            k_all, v_all, kv_pos = k, v, positions
        else:
            # ring-buffer indexing: sliding-window caches allocate max_len ==
            # window and wrap (harmless for full caches: index < max_len)
            idx = cache["index"] % cl
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
            cpos = jax.lax.dynamic_update_slice_in_dim(cache["pos"], positions.astype(jnp.int32), idx, axis=1)
            new_cache = {"k": ck, "v": cv, "pos": cpos, "index": idx + s}
            k_all, v_all, kv_pos = ck, cv, cpos
    else:
        new_cache = None
        k_all, v_all, kv_pos = k, v, positions

    if use_pallas and cache is None and cfg.sliding_window is None:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k_all, v_all, causal=True)
    elif cfg.chunk_kv and k_all.shape[1] > cfg.chunk_kv:
        out = _chunked_attn(q, k_all, v_all, positions, kv_pos,
                            cfg.sliding_window, cfg.chunk_kv)
    else:
        out = _full_causal_attn(q, k_all, v_all, positions, kv_pos,
                                cfg.sliding_window)
    out = out.reshape(b, s, h * hd) @ p["wo"].astype(x.dtype)
    return out, new_cache


def attention_cache_init(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.full((batch, max_len), jnp.iinfo(jnp.int32).max, jnp.int32),
        "index": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_init(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    p, a = {}, {}
    p["wi"], a["wi"] = _normal(k1, (d_model, d_ff), 1 / math.sqrt(d_model), dtype), ("embed", "ff")
    p["wg"], a["wg"] = _normal(k2, (d_model, d_ff), 1 / math.sqrt(d_model), dtype), ("embed", "ff")
    p["wo"], a["wo"] = _normal(k3, (d_ff, d_model), 1 / math.sqrt(d_ff), dtype), ("ff", "embed")
    return p, a


def swiglu_apply(p, x):
    h = jax.nn.silu(x @ p["wg"].astype(x.dtype)) * (x @ p["wi"].astype(x.dtype))
    return h @ p["wo"].astype(x.dtype)


def gelu_mlp_init(key, d_model, d_ff, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    p, a = {}, {}
    p["wi"], a["wi"] = _normal(k1, (d_model, d_ff), 1 / math.sqrt(d_model), dtype), ("embed", "ff")
    p["wo"], a["wo"] = _normal(k2, (d_ff, d_model), 1 / math.sqrt(d_ff), dtype), ("ff", "embed")
    return p, a


def gelu_mlp_apply(p, x):
    return jax.nn.gelu(x @ p["wi"].astype(x.dtype)) @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def embedding_init(key, vocab, d_model, dtype=jnp.float32):
    return ({"table": _normal(key, (vocab, d_model), 0.02, dtype)},
            {"table": ("vocab", "embed")})


def embedding_apply(p, ids, compute_dtype=None):
    out = jnp.take(p["table"], ids, axis=0)
    return out.astype(compute_dtype) if compute_dtype else out


def unembed_apply(p, x):
    """Tied or untied head: p['table'] (V, D) -> logits (..., V)."""
    return x @ p["table"].astype(x.dtype).T


# ---------------------------------------------------------------------------
# conv primitives (NHWC) for the paper's CNN families
# ---------------------------------------------------------------------------

def conv_init(key, in_ch, out_ch, ksize, *, dtype=jnp.float32):
    fan_in = in_ch * ksize * ksize
    return ({"w": _normal(key, (ksize, ksize, in_ch, out_ch), math.sqrt(2.0 / fan_in), dtype)},
            {"w": (None, None, "chan_in", "chan")})


def conv_apply(p, x, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def sepconv_init(key, in_ch, out_ch, ksize, *, dtype=jnp.float32):
    """Depthwise-separable conv (Xception building block)."""
    kd, kp = jax.random.split(key)
    p, a = {}, {}
    p["dw"] = _normal(kd, (ksize, ksize, 1, in_ch), math.sqrt(2.0 / (ksize * ksize)), dtype)
    a["dw"] = (None, None, None, "chan")
    p["pw"] = _normal(kp, (1, 1, in_ch, out_ch), math.sqrt(2.0 / in_ch), dtype)
    a["pw"] = (None, None, "chan_in", "chan")
    return p, a


def _shifted_sum(x, k):
    """``sum_ij x[h + i - r, w + j - r] * k[i, j]`` over a (2r+1)^2 window
    with zero padding, in float32: x (B, H, W, C), k (K, K, C)."""
    r = k.shape[0] // 2
    h, w = x.shape[1], x.shape[2]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (r, r), (r, r), (0, 0)))
    k = k.astype(jnp.float32)
    out = None
    for i in range(k.shape[0]):
        for j in range(k.shape[1]):
            t = xp[:, i:i + h, j:j + w, :] * k[i, j]
            out = t if out is None else out + t
    return out


@jax.custom_vjp
def depthwise_conv(x, k):
    """Stride-1 SAME depthwise convolution, x (B, H, W, C) NHWC and
    k (K, K, 1, C) HWIO, as K*K shifted multiply-adds in float32 on the
    vector units.  The backward pass is written the same way (the input's
    gradient is the forward with the kernel flipped; the kernel's is K*K
    reductions), so neither pass materialises more than its output: the
    grouped ``conv_general_dilated`` at ``Precision.HIGHEST`` splits its
    operands into bfloat16 parts and, at 768x768x192 and batch 4, its
    gradient alone needs 11.6 GB of a v5e's 15.75."""
    return _shifted_sum(x, k[:, :, 0]).astype(x.dtype)


def _depthwise_fwd(x, k):
    return depthwise_conv(x, k), (x, k)


def _depthwise_bwd(res, g):
    x, k = res
    dx = _shifted_sum(g, k[::-1, ::-1, 0]).astype(x.dtype)
    r = k.shape[0] // 2
    h, w = x.shape[1], x.shape[2]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (r, r), (r, r), (0, 0)))
    gf = g.astype(jnp.float32)
    dk = jnp.stack([jnp.stack([
        jnp.sum(gf * xp[:, i:i + h, j:j + w, :], axis=(0, 1, 2))
        for j in range(k.shape[1])]) for i in range(k.shape[0])])
    return dx, dk[:, :, None, :].astype(k.dtype)


depthwise_conv.defvjp(_depthwise_fwd, _depthwise_bwd)


def sepconv_apply(p, x):
    return sepconv_concat_apply(p, (x,))


def sepconv_concat_apply(p, xs):
    """``sepconv_apply`` of the channel concatenation of ``xs`` without
    materialising it: the depthwise step is per channel, and the
    pointwise product is summed over each part's rows of the kernel."""
    dt = xs[0].dtype
    dw, pw = p["dw"].astype(dt), p["pw"].astype(dt)
    out, lo = None, 0
    for x in xs:
        hi = lo + x.shape[-1]
        h = jax.lax.conv_general_dilated(
            depthwise_conv(x, dw[..., lo:hi]), pw[:, :, lo:hi], (1, 1),
            "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        out = h if out is None else out + h
        lo = hi
    return out


def avg_pool(x, window=2, stride=2):
    return jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, window, window, 1), (1, stride, stride, 1),
        "VALID") / (window * window)


def max_pool(x, window=2, stride=2, padding="VALID"):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1), (1, stride, stride, 1), padding)


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))


def upsample2x(x):
    b, h, w, c = x.shape
    return jax.image.resize(x, (b, 2 * h, 2 * w, c), method="nearest")


def param_count(params) -> int:
    return int(sum(np.prod(a.shape) for a in jax.tree.leaves(params)))


def param_bytes(params) -> int:
    return int(sum(np.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(params)))
