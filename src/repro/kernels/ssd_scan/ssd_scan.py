"""Mamba2 SSD — Pallas TPU kernel for the chunk-local compute.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): the chunk-quadratic
term and the per-chunk state summaries are MXU matmuls per (batch, chunk,
head) grid cell, all operands tiled into VMEM; the strictly-sequential
inter-chunk recurrence (a tiny (h,p,n) scan) and the rank-1 inter-chunk
output correction stay outside in XLA, where a `lax.scan` over nc steps is
already optimal (it is bandwidth-trivial next to the chunk matmuls).

Per grid cell (b, c, h):
  xbar tile : (q, p)   VMEM     (dt-discretized inputs)
  B/C tile  : (q, n)   VMEM     (GQA-style group indexing h -> h // rep)
  la tile   : (q, 1)   VMEM     (log decay, fp32)
  outputs   : y_intra (q, p), state (n, p), decay vectors (q, 1)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compat import INTERPRET, CompilerParams

NEG_INF = -1e30


def _kernel(xbar_ref, la_ref, b_ref, c_ref, y_ref, st_ref, dte_ref, dfs_ref):
    xbar = xbar_ref[0, 0, :, 0, :].astype(jnp.float32)      # (q, p)
    la = la_ref[0, 0, :, :].astype(jnp.float32)             # (q, 1)
    B = b_ref[0, 0, :, 0, :].astype(jnp.float32)            # (q, n)
    C = c_ref[0, 0, :, 0, :].astype(jnp.float32)            # (q, n)
    q = xbar.shape[0]

    cs = jnp.cumsum(la, axis=0)                             # (q, 1) inclusive
    # L[i, j] = exp(cs_i - cs_j) for j <= i else 0
    seg = cs - cs.reshape(1, q)                             # (q, q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lmat = jnp.where(cols <= rows, jnp.exp(seg), 0.0)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (q,q)
    y = jax.lax.dot_general(scores * lmat, xbar,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (q,p)

    total = cs[q - 1:q, :]                                  # (1, 1)
    dte = jnp.exp(total - cs)                               # (q, 1) to-end
    dfs = jnp.exp(cs)                                       # (q, 1) from-start
    state = jax.lax.dot_general(B * dte, xbar,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (n,p)

    y_ref[0, 0, :, 0, :] = y.astype(y_ref.dtype)
    st_ref[0, 0, 0, :, :] = state
    dte_ref[0, 0, :, :] = dte
    dfs_ref[0, 0, :, :] = dfs


def ssd_chunk_kernel(xbar, la, B, C, *, interpret=INTERPRET):
    """xbar: (b, nc, q, h, p)  la: (b, nc, q, h)  B, C: (b, nc, q, g, n).
    Returns y_intra (b,nc,q,h,p) f32, states (b,nc,h,n,p) f32,
            dte (b,nc,q,h) f32, dfs (b,nc,q,h) f32."""
    b, nc, q, h, p = xbar.shape
    g, n = B.shape[3], B.shape[4]
    rep = h // g
    grid = (b, nc, h)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q, 1, p),
                         lambda bb, cc, hh: (bb, cc, 0, hh, 0)),
            pl.BlockSpec((1, 1, q, 1),
                         lambda bb, cc, hh: (bb, cc, 0, hh)),
            pl.BlockSpec((1, 1, q, 1, n),
                         lambda bb, cc, hh: (bb, cc, 0, hh // rep, 0)),
            pl.BlockSpec((1, 1, q, 1, n),
                         lambda bb, cc, hh: (bb, cc, 0, hh // rep, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, 1, p),
                         lambda bb, cc, hh: (bb, cc, 0, hh, 0)),
            pl.BlockSpec((1, 1, 1, n, p),
                         lambda bb, cc, hh: (bb, cc, hh, 0, 0)),
            pl.BlockSpec((1, 1, q, 1),
                         lambda bb, cc, hh: (bb, cc, 0, hh)),
            pl.BlockSpec((1, 1, q, 1),
                         lambda bb, cc, hh: (bb, cc, 0, hh)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, q, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, n, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, q, h), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, q, h), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xbar, la, B, C)
    return out
