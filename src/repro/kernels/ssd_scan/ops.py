"""Jitted SSD wrapper: Pallas chunk kernel + XLA inter-chunk recurrence."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.compat import INTERPRET
from repro.kernels.ssd_scan.ssd_scan import ssd_chunk_kernel


@partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, A, B, C, chunk):
    """x: (b,l,h,p)  dt: (b,l,h) (post-softplus)  A: (h,) positive
    B, C: (b,l,g,n).  Returns (y (b,l,h,p), final_state (b,h,p,n))."""
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0
    nc, q = l // chunk, chunk
    rep = h // g

    xbar = (x * dt[..., None]).reshape(b, nc, q, h, p)
    la = (-dt * A).astype(jnp.float32).reshape(b, nc, q, h)
    Bc = B.reshape(b, nc, q, g, n)
    Cc = C.reshape(b, nc, q, g, n)

    y_intra, states, dte, dfs = ssd_chunk_kernel(
        xbar, la, Bc, Cc, interpret=INTERPRET)

    # inter-chunk recurrence (sequential over nc, tiny (h,n,p) carry)
    a_last = jnp.exp(la.sum(axis=2))                        # (b, nc, h)

    def body(s, inp):
        st, al = inp
        s_new = s * al[:, :, None, None] + st
        return s_new, s

    s0 = jnp.zeros((b, h, n, p), jnp.float32)
    final, prev = jax.lax.scan(
        body, s0, (states.transpose(1, 0, 2, 3, 4),
                   a_last.transpose(1, 0, 2)))
    prev = prev.transpose(1, 0, 2, 3, 4)                    # (b,nc,h,n,p)

    Crep = jnp.repeat(Cc, rep, axis=3).astype(jnp.float32)  # (b,nc,q,h,n)
    y_inter = jnp.einsum("bcqhn,bchnp,bcqh->bcqhp", Crep, prev, dfs)
    y = (y_intra + y_inter).reshape(b, l, h, p).astype(x.dtype)
    return y, final.transpose(0, 1, 3, 2)                   # (b,h,p,n)
