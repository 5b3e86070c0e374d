"""Flash attention for TPU (Pallas): block-wise online softmax.

Tiling (per DESIGN.md §6): the grid is (batch, q_head, q_block, kv_block);
the kv_block axis is the innermost, sequentially-iterated ("arbitrary")
dimension, accumulating into VMEM scratch:

  q tile   : (block_q, d)        VMEM
  k/v tile : (block_k, d)        VMEM (indexed by the GQA group of the head)
  acc      : (block_q, d)  fp32  VMEM scratch
  m, l     : (block_q, 128) fp32 VMEM scratch (lane-replicated running max/sum)

block_q/block_k default to 128 — MXU-aligned (128x128 systolic array) and
8-lane friendly.  Causal masking skips fully-masked kv blocks via
``pl.when`` on the block indices (structural win: ~2x for causal prefill).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.compat import INTERPRET, CompilerParams

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, block_q, block_k, seq_q, seq_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: kv block strictly after the q block is fully masked -> skip.
    # (kv positions are offset by seq_k - seq_q when kv is longer, e.g. a
    #  prefilled cache; here seq_q == seq_k for the training/prefill path.)
    q_start = qi * block_q
    k_start = ki * block_k
    should_run = jnp.logical_or(
        jnp.logical_not(causal), k_start <= q_start + block_q - 1)

    @pl.when(should_run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            logits = jnp.where(cols <= rows, logits, NEG_INF)

        m_prev = m_ref[:, :1]                          # (bq, 1)
        m_cur = logits.max(axis=-1, keepdims=True)     # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)                    # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_new = l_ref[:, :1] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(
            o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, block_q=128, block_k=128,
                           interpret=INTERPRET):
    """q: (B, H, S, D); k, v: (B, KV, S, D).  Returns (B, H, S, D)."""
    b, h, s, d = q.shape
    kv, t = k.shape[1], k.shape[2]
    assert h % kv == 0 and t == s, (q.shape, k.shape)
    rep = h // kv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0
    grid = (b, h, s // block_q, t // block_k)
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=s, seq_k=t)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qi, ki: (bb, hh // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bb, hh, qi, ki: (bb, hh // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
