"""Computation accounting (paper Tables 5/6): server FLOPs, average client
FLOPs, and model-averaging FLOPs, per epoch.

Per-segment forward FLOPs come from XLA's ``cost_analysis()`` of the jitted
segment application — the compiler's count, not a hand model.  Training
FLOPs use the standard fwd+bwd = 3x forward rule (backward ~ 2x forward for
matmul-dominated nets).  Averaging FLOPs are analytic: (n_clients adds + 1
scale) per parameter, counted once per epoch exactly as the paper does.
"""

from __future__ import annotations

import dataclasses

import jax

from repro.core.partition import SplitAdapter
from repro.models.layers import param_count

TRAIN_FACTOR = 3.0     # fwd + bwd


def flops_of(fn, *args) -> float:
    """HLO FLOPs of fn(*args) on the current backend."""
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return float(cost.get("flops", 0.0))


@dataclasses.dataclass(frozen=True)
class FlopsProfile:
    method: str
    server_tflops: float
    avg_client_tflops: float
    averaging_mflops: float


def segment_fwd_flops(adapter: SplitAdapter, example_batch: dict) -> dict:
    """Forward FLOPs per segment, per batch."""
    params = adapter.init(jax.random.key(0))
    out = {}
    x = adapter.inputs(example_batch)
    for seg in adapter.seg_names:
        out[seg] = flops_of(
            lambda p, xx: adapter.apply_seg(seg, p, xx, example_batch, True),
            params[seg], x)
        x = adapter.apply_seg(seg, params[seg], x, example_batch, True)
    return out


def flops_per_epoch(method: str, adapter: SplitAdapter, example_batch: dict,
                    n_train: list[int], batch_size: int,
                    seg_fwd: dict | None = None) -> FlopsProfile:
    n_clients = len(n_train)
    total_batches = sum(n // batch_size for n in n_train)

    if seg_fwd is None:
        seg_fwd = segment_fwd_flops(adapter, example_batch)
    client_fwd = seg_fwd["front"] + seg_fwd.get("tail", 0.0)
    server_fwd = seg_fwd["middle"]
    full_fwd = sum(seg_fwd.values())

    params = jax.eval_shape(adapter.init, jax.random.key(0))
    p_all = param_count(params)
    p_client = param_count(params["front"]) + (
        param_count(params["tail"]) if adapter.nls else 0)
    p_middle = param_count(params["middle"])

    if method == "centralized":
        return FlopsProfile(method,
                            TRAIN_FACTOR * full_fwd * total_batches / 1e12,
                            0.0, 0.0)
    if method == "fl":
        avg_client = TRAIN_FACTOR * full_fwd * total_batches / n_clients
        averaging = p_all * (n_clients + 1)
        return FlopsProfile(method, 0.0, avg_client / 1e12, averaging / 1e6)

    server = TRAIN_FACTOR * server_fwd * total_batches
    avg_client = TRAIN_FACTOR * client_fwd * total_batches / n_clients
    averaging = 0.0
    if method.startswith("sflv2"):
        averaging = p_client * (n_clients + 1)
    elif method.startswith("sflv3"):
        averaging = p_middle * (n_clients + 1)
    elif method.startswith("sflv1"):
        averaging = p_all * (n_clients + 1)
    return FlopsProfile(method, server / 1e12, avg_client / 1e12,
                        averaging / 1e6)
