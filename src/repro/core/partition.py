"""Cut-layer model partitioning — the structural heart of SL / SplitFed.

Both model families (transformer LMs, unit-list CNNs) are wrapped into a
uniform ``SplitAdapter`` so every strategy in ``repro.core.strategies`` is
architecture-agnostic.  Segments:

  * ``front``  — at the client; raw inputs never leave it.
  * ``middle`` — at the server (the bulk of the compute).
  * ``tail``   — at the client again, only in the non-label-sharing
    (U-shaped) configuration; holds the head so labels never leave either.

Activations crossing segment boundaries may be arbitrary pytrees (the U-Net
front emits (hidden, skips)); communication accounting sums leaf bytes.

``full_loss`` runs each segment under ``jax.named_scope(<segment>)`` and
the boundary hook under ``jax.named_scope("cut")``, so every operation of
a compiled training program names the segment it belongs to
(``repro.obs.scopes``); scopes are HLO metadata only.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SplitAdapter:
    """Uniform three-segment view of a model for the distributed strategies."""
    name: str
    seg_names: tuple[str, ...]                 # ("front","middle"[,"tail"])
    init: Callable[[Any], Any]                 # key -> params {seg: tree}
    inputs: Callable[[dict], Any]              # batch -> x0
    apply_seg: Callable[..., Any]              # (seg, seg_params, x, batch, train) -> x
    loss_from_output: Callable[[Any, dict], Any]
    scores_from_output: Callable[[Any], Any]   # output -> probabilities
    per_example_loss: Callable[[Any, dict], Any] | None = None  # -> (B,)

    @property
    def nls(self) -> bool:
        return "tail" in self.seg_names

    # -- composition helpers -------------------------------------------------
    def full_loss(self, params, batch, train=True, boundary=None,
                  weights=None):
        """``boundary``: optional fn applied to every cross-segment
        activation pytree (the repro.wire transport hook — the server sees
        what actually crossed the wire).  ``weights``: optional (B,)
        per-example weights — the loss becomes a weighted mean over the
        per-example losses, which is how the compiled engine masks padding
        rows out of a pad-and-mask remainder batch."""
        x = self.inputs(batch)
        last = len(self.seg_names) - 1
        for i, seg in enumerate(self.seg_names):
            with jax.named_scope(seg):
                x = self.apply_seg(seg, params[seg], x, batch, train)
            if boundary is not None and i < last:
                with jax.named_scope("cut"):
                    x = boundary(x)
        if weights is None:
            return self.loss_from_output(x, batch)
        if self.per_example_loss is None:
            raise ValueError(
                f"adapter {self.name!r} has no per_example_loss; weighted "
                "(pad-and-mask) losses need one")
        pe = self.per_example_loss(x, batch).astype(jnp.float32)
        w = weights.astype(jnp.float32)
        return (pe * w).sum() / jnp.maximum(w.sum(), 1.0)

    def full_scores(self, params, batch):
        x = self.inputs(batch)
        for seg in self.seg_names:
            x = self.apply_seg(seg, params[seg], x, batch, False)
        return self.scores_from_output(x)

    # -- boundary shape accounting (for repro.core.comm) ---------------------
    def boundary_specs(self, example_batch: dict, params=None) -> dict:
        """ShapeDtypeStructs of every segment-boundary activation."""
        if params is None:
            params = jax.eval_shape(self.init, jax.random.key(0))

        def front(p, b):
            return self.apply_seg("front", p, self.inputs(b), b, True)

        specs = {}
        h = jax.eval_shape(front, params["front"], example_batch)
        specs["front->middle"] = h
        if self.nls:
            def middle(p, hh, b):
                return self.apply_seg("middle", p, hh, b, True)
            h2 = jax.eval_shape(middle, params["middle"], h, example_batch)
            specs["middle->tail"] = h2
        return specs


# ---------------------------------------------------------------------------
# adapters
# ---------------------------------------------------------------------------

def cnn_adapter(model) -> SplitAdapter:
    """Wrap a repro.models.cnn.CNNModel."""

    def init(key):
        return model.init_params(key)

    def inputs(batch):
        return batch["image"]

    def apply_seg(seg, seg_params, x, batch, train=False):
        return model.apply_segment(seg_params, seg, x, train)

    def loss_from_output(out, batch):
        from repro.models.cnn import bce_loss
        return bce_loss(out, batch["label"])

    def scores_from_output(out):
        return jax.nn.sigmoid(out.reshape(-1).astype(jnp.float32))

    def per_example_loss(out, batch):
        logits = out.reshape(-1).astype(jnp.float32)
        labels = batch["label"].reshape(-1).astype(jnp.float32)
        return (jnp.maximum(logits, 0) - logits * labels
                + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    return SplitAdapter(model.name, tuple(model.seg_names), init, inputs,
                        apply_seg, loss_from_output, scores_from_output,
                        per_example_loss)


def lm_adapter(model) -> SplitAdapter:
    """Wrap a repro.models.transformer.TransformerLM (built with cut/nls)."""
    seg_names = tuple(s.name for s in model.segments)
    seg_index = {s.name: i for i, s in enumerate(model.segments)}

    def init(key):
        return model.init_params(key)

    def inputs(batch):
        return batch["tokens"][:, :-1]

    def apply_seg(seg, seg_params, x, batch, train=False):
        i = seg_index[seg]
        out, _, aux = model.apply({seg: seg_params}, x,
                                  positions=_positions(batch, model),
                                  frontend_emb=batch.get("frontend_emb"),
                                  train=train, segment_range=(i, i + 1))
        # carry aux loss along with activations so it reaches the loss
        if seg == seg_names[-1]:
            return out
        return out

    def _positions(batch, model):
        b, s = batch["tokens"].shape
        s -= 1
        fe = batch.get("frontend_emb")
        total = s + (fe.shape[1] if fe is not None else 0)
        return jnp.broadcast_to(jnp.arange(total, dtype=jnp.int32), (b, total))

    def _token_nll(logits, batch):
        labels = batch["tokens"][:, 1:]
        if batch.get("frontend_emb") is not None:
            logits = logits[:, -labels.shape[1]:]
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logits.astype(jnp.float32),
                                 labels[..., None], axis=-1)[..., 0]
        return lse - ll                              # (B, S)

    def loss_from_output(logits, batch):
        return _token_nll(logits, batch).mean()

    def per_example_loss(logits, batch):
        return _token_nll(logits, batch).mean(axis=-1)

    def scores_from_output(logits):
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    return SplitAdapter(model.cfg.name, seg_names, init, inputs, apply_seg,
                        loss_from_output, scores_from_output,
                        per_example_loss)


PRECISIONS = ("fp32", "bf16")


def cast_adapter(adapter: SplitAdapter, precision: str) -> SplitAdapter:
    """Mixed-precision view of an adapter: compute in bf16, master in fp32.

    With ``precision="bf16"`` every TRAINING segment application casts its
    floating params and activations to bfloat16 before the underlying
    ``apply_seg`` — so the forward/backward matmuls run in bf16 while the
    params the optimizer owns (and therefore FedAvg client averaging and
    the server-Adam moments) stay full fp32 masters: the cast sits inside
    the loss, so ``jax.grad`` cotangents flow back through the ``astype``
    and arrive fp32.  Losses are already reduced in fp32 by every adapter,
    and evaluation (``train=False``) is untouched — clients score with
    their own full-precision segments, matching the paper's eval protocol.

    Boundary specs inherit the cast (train-time smashed activations ARE
    bf16 on the wire), so transport byte accounting stays honest.
    ``precision="fp32"`` returns the adapter unchanged.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r} "
                         f"(one of {PRECISIONS})")
    if precision == "fp32":
        return adapter

    def _cast(tree):
        return jax.tree.map(
            lambda l: l.astype(jnp.bfloat16)
            if jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating) else l,
            tree)

    inner = adapter.apply_seg

    def apply_seg(seg, seg_params, x, batch, train=False):
        if not train:
            return inner(seg, seg_params, x, batch, train)
        return inner(seg, _cast(seg_params), _cast(x), batch, train)

    return dataclasses.replace(adapter, apply_seg=apply_seg)


def leaf_bytes(tree) -> int:
    return int(sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for l in jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# stacked-tree helpers — per-client pytrees with a leading hospital axis
# ---------------------------------------------------------------------------

def stack_trees(trees):
    """List of identically-shaped pytrees -> one tree with leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def unstack_tree(tree, n):
    """Inverse of ``stack_trees``: leading axis back to a list of trees."""
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]


def tree_take(tree, i):
    """Select hospital ``i``'s slice from a stacked tree (traceable)."""
    return jax.tree.map(lambda x: x[i], tree)


def tree_put(tree, i, sub):
    """Scatter ``sub`` back into hospital ``i``'s slice (traceable)."""
    return jax.tree.map(lambda x, y: x.at[i].set(y), tree, sub)


def tree_select(flag, new, old):
    """``new`` where ``flag`` (scalar bool) else ``old`` — the pad-and-mask
    engine's way of turning an invalid (padding) step into a no-op."""
    return jax.tree.map(lambda a, b: jnp.where(flag, a, b), new, old)
