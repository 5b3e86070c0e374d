"""Transport — the codec hook threaded through real training.

``Transport.boundary`` is a differentiable in-graph roundtrip applied to the
cut-layer activation pytree between segments (front->middle and, for NLS,
middle->tail): the server trains on exactly what it would have received over
the wire.  Lossy codecs backpropagate straight-through (see
``repro.wire.codec``).

Byte accounting happens host-side from boundary SHAPES (the roundtrip
itself never materializes a payload inside the jitted step): strategies
call ``account`` once per training step and the transport accumulates
exact on-wire and raw byte counters, cached per (adapter, batch shape).
Evaluation paths are not accounted (and not compressed) — clients score
with their own full-precision segments, matching the paper's eval
protocol.

``record_epoch`` is the analytic->timeline bridge hook: strategies hand
the transport each trained epoch's schedule signature (method kind,
interleaving schedule, per-client batch counts, per-leg byte sizes), and
``repro.wire.simulator.timeline_from_accounting`` expands those summaries
back into the exact per-step transfer DAG the event engine replays —
identical whichever engine trained, per-step or analytic accounting.
"""

from __future__ import annotations

import dataclasses
import math

import jax

from repro.wire.codec import Codec, make_codec, tree_roundtrip, \
    tree_wire_bytes


@dataclasses.dataclass(frozen=True)
class EpochSchedule:
    """One trained epoch's schedule signature (recorded by
    ``Transport.record_epoch``) — everything the wire simulator needs to
    expand the epoch's analytic accounting back into per-step transfers:
    the method kind, the client interleaving, per-client train batch
    counts, and the per-leg on-wire/raw byte sizes (``core.comm.leg_sizes``
    through this transport's codec).

    Under per-round client subsampling ``client_set`` records which
    global clients participated this round; unsampled clients carry a
    zero ``tr_counts`` entry, so the expansion naturally emits no
    transfers for them."""
    kind: str                   # "sl" | "sflv2" | "sflv3" | "sflv1"
    schedule: str               # "ac" | "am"
    tr_counts: tuple            # per-client train batch counts
    legs: dict                  # leg name -> bytes (act_fm, act_mt, ...)
    nls: bool
    client_set: tuple | None = None   # sampled global client ids (or None)


@dataclasses.dataclass
class Transport:
    codec: Codec
    #: allow the cut-layer boundary to run as ONE fused Pallas kernel when
    #: the codec supports it (``Codec.fusable``) — roundtrip and cut noise
    #: in a single pass, bit-equal to the unfused composition.  Accounting
    #: is analytic either way; set False to force the unfused reference.
    fuse: bool = True
    bytes_on_wire: float = 0.0
    bytes_raw: float = 0.0
    steps: int = 0
    epoch_log: list = dataclasses.field(default_factory=list, repr=False)
    _cache: dict = dataclasses.field(default_factory=dict, repr=False)

    def __post_init__(self):
        self.codec = make_codec(self.codec)

    # -- in-graph ------------------------------------------------------------
    def boundary(self, tree):
        """Encode+decode every leaf crossing a segment boundary.

        With ``fuse`` and a fusable codec the quantize+dequantize pair runs
        as one ``kernels/cut_fuse`` pass — bit-equal, half the HBM traffic.
        """
        if self.fuse and self.codec.fusable:
            return jax.tree.map(self.codec.fused_roundtrip, tree)
        return tree_roundtrip(self.codec, tree)

    @property
    def fused_codec(self):
        """The codec when roundtrip+noise may fuse into one kernel, else
        None — what the step builders hand ``privacy.boundary_with_key``."""
        return self.codec if self.fuse and self.codec.fusable else None

    # -- host-side accounting ------------------------------------------------
    @staticmethod
    def _shape_key(adapter, batch: dict):
        """Cache key: batch shape signature PLUS the adapter itself.

        Keying on shapes alone silently reused one adapter's boundary
        sizes for another when a single ``Transport`` was shared across
        adapters / cut points; the adapter (a frozen dataclass, hashable,
        kept alive by the cache) pins the entry to its boundary.
        """
        return (adapter,
                tuple(sorted((k, tuple(v.shape), str(v.dtype))
                             for k, v in batch.items())))

    def account(self, adapter, batch: dict, train: bool = True,
                count: int = 1):
        """Record ``count`` steps' boundary traffic (activations up + grads
        down per step).

        Cached on the (adapter, batch shape) signature, so per-step cost
        after the first call is a dict lookup.  The compiled engine
        accounts a whole epoch (or whole run) analytically in one call per
        hospital (``count=n_batches``) instead of once per host-loop step.
        """
        key = ("bytes", *self._shape_key(adapter, batch))
        if key not in self._cache:
            specs = adapter.boundary_specs(batch)
            from repro.core.partition import leaf_bytes
            wire = sum(tree_wire_bytes(self.codec, t)
                       for t in specs.values())
            raw = sum(leaf_bytes(t) for t in specs.values())
            arrays = sum(len(jax.tree.leaves(t)) for t in specs.values())
            self._cache[key] = (wire, raw, arrays)
        wire, raw, _ = self._cache[key]
        legs = 2 if train else 1           # train: + gradient leg back
        self.bytes_on_wire += count * legs * wire
        self.bytes_raw += count * legs * raw
        self.steps += count

    def cut_arrays(self, adapter) -> int:
        """Arrays that cross ``adapter``'s boundaries per step, as the
        accounting so far found them (0 before any step was accounted)."""
        return max((v[2] for k, v in self._cache.items()
                    if k[0] == "bytes" and k[1] == adapter), default=0)

    def record_epoch(self, adapter, example_batch: dict, kind: str,
                     schedule: str, n_batches, client_set=None) -> None:
        """Append one trained epoch's schedule signature to ``epoch_log``.

        Called once per epoch by the SL/SFL strategies under BOTH engines
        (the stepwise per-step path and the compiled analytic path record
        identical signatures), which is what makes
        ``simulator.timeline_from_accounting`` engine-independent.
        ``client_set`` marks a participating round's sampled clients.
        """
        key = ("legs", *self._shape_key(adapter, example_batch))
        if key not in self._cache:
            from repro.core.comm import leg_sizes
            self._cache[key] = leg_sizes(adapter, example_batch,
                                         codec=self.codec)
        self.epoch_log.append(EpochSchedule(
            kind, schedule, tuple(int(n) for n in n_batches),
            self._cache[key], adapter.nls,
            None if client_set is None
            else tuple(int(c) for c in client_set)))

    @property
    def compression_ratio(self) -> float:
        if self.bytes_on_wire <= 0:
            return math.nan
        return self.bytes_raw / self.bytes_on_wire

    def reset(self):
        self.bytes_on_wire = self.bytes_raw = 0.0
        self.steps = 0
        self.epoch_log.clear()

    def summary(self) -> dict:
        return {"codec": self.codec.name, "steps": self.steps,
                "bytes_on_wire": self.bytes_on_wire,
                "bytes_raw": self.bytes_raw,
                "compression_ratio": self.compression_ratio}


def boundary_error(transport_or_codec, adapter, params, batch: dict) -> dict:
    """Reconstruction error of the codec on REAL boundary activations."""
    codec = (transport_or_codec.codec
             if isinstance(transport_or_codec, Transport)
             else make_codec(transport_or_codec))
    x = adapter.inputs(batch)
    errs = {}
    for i, seg in enumerate(adapter.seg_names[:-1]):
        x = adapter.apply_seg(seg, params[seg], x, batch, False)
        leaves = jax.tree.leaves(x)
        errs[f"{seg}->"] = [codec.error(l) for l in leaves]
        x = jax.tree.map(codec.roundtrip, x)
    return errs
