"""Jitted public wrapper for the flash-attention kernel.

Accepts the model-layer layout (B, S, H, D)/(B, S, KV, D) and handles the
transpose to the kernel's (B, H, S, D) layout.  On CPU the kernel runs in
interpret mode (the TPU target compiles the same kernel body).
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.compat import INTERPRET
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, causal=True, block_q=128, block_k=128):
    """q: (B, S, H, D); k, v: (B, S, KV, D).  Returns (B, S, H, D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_pallas(qt, kt, vt, causal=causal, block_q=block_q,
                                 block_k=block_k, interpret=INTERPRET)
    return out.transpose(0, 2, 1, 3)
