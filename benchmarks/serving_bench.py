"""Serving benchmark — the deploy-side latency/throughput trajectory, next
to ``engine_bench`` (training) and ``roofline`` (kernels).

Two sweeps per model family (densenet-mini, unet-mini):

  * **batch sweep** (closed loop): ``BucketScorer.score`` timed directly
    at every ladder bucket — per-dispatch p50/p99 wall-clock and images/s
    at that batch size.  This is the service's intrinsic latency ladder:
    what one padded-bucket dispatch costs once compilation is out of the
    hot path.
  * **arrival sweep** (open loop): single-image requests submitted to a
    live ``ScreeningService`` at fixed arrival rates; per-request total
    latency p50/p99, achieved throughput, and the mean coalesced batch
    size.  This measures what the QUEUE adds on top of the ladder — the
    batching/max-wait tradeoff under load.

Steady-state serving must issue ZERO fresh compiles: every timed section
asserts ``BucketScorer.n_compiles`` is frozen at its construction count
(the ladder is pre-lowered; any drift is a bug, and the bench exits 1).

Writes ``benchmarks/results/BENCH_serving.json``:

    {"families": ["densenet", "unet"],
     "batch_sweep": [{"family", "bucket", "p50_ms", "p99_ms",
                      "images_per_sec"}, ...],
     "arrival_sweep": [{"family", "rate_rps", "p50_ms", "p99_ms",
                        "throughput_rps", "batch_n_mean"}, ...],
     "n_compiles": {"densenet": 7, ...}}

``--trace PATH`` additionally writes the arrival sweep's merged request
trace (queue-wait / pad / dispatch / readback spans per request batch, one
``repro.obs`` lane per family) as Chrome-trace JSON — the artifact the CI
slow job uploads.

``--check-against BENCH.json`` gates p99 latency: a batch-sweep or
arrival-sweep p99 more than 20% above the committed value (plus a 1 ms
absolute slack floor — sub-ms buckets jitter more than 20% on shared CPU
runners) fails with exit 1, mirroring the engine bench's speedup gate.

  PYTHONPATH=src python -m benchmarks.serving_bench [--smoke]
      [--families densenet,unet] [--buckets 1,2,4,8,16,32,64]
      [--rates 20,50,100] [--reps N] [--check-against PATH] [--trace PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

from repro import optim as O
from repro.core.partition import cnn_adapter
from repro.core.strategies import make_strategy
from repro.data.synthetic import make_cxr_clients
from repro.models.cnn import (DenseNetConfig, UNetConfig, build_densenet,
                              build_unet)
from repro.obs.trace import PID_SERVING, merge_events, write_chrome_trace
from repro.serving import BucketScorer, ScreeningService

OUT = os.path.join(os.path.dirname(__file__), "results",
                   "BENCH_serving.json")

FAMILIES = {
    # mini configs: the bench measures the serving machinery (pad,
    # dispatch, queue) at CI scale, not full DenseNet-121 conv throughput
    "densenet": lambda: build_densenet(
        DenseNetConfig(growth=4, blocks=(1, 2), stem_ch=8, cut_layer=1)),
    "unet": lambda: build_unet(
        UNetConfig(widths=(8, 16), cut_layer=1)),
}


def trained_servable(family: str, image_size: int):
    """One FL round over tiny synthetic hospitals -> the family's export
    (the bench serves a REAL trained artifact, not random params)."""
    clients = make_cxr_clients(seed=0, n_clients=3, train_per_client=8,
                               val_per_client=4, test_per_client=8,
                               image_size=image_size)
    adapter = cnn_adapter(FAMILIES[family]())
    strat = make_strategy("fl", adapter, lambda: O.adam(1e-3), len(clients))
    state = strat.setup(jax.random.key(0))
    state, _ = strat.run_epoch(state, [c.train for c in clients],
                               np.random.default_rng(0), 4)
    img = clients[0].test["image"]
    return strat.export(state, meta={"bench": family}), img


def pctl(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def batch_sweep(family, scorer, images, reps):
    """Closed-loop per-bucket dispatch latency (one warm pass, then
    ``reps`` timed calls per bucket)."""
    rows = []
    rng = np.random.default_rng(0)
    pool = images[rng.integers(0, len(images),
                               size=max(scorer.buckets[-1], len(images)))]
    for b in scorer.buckets:
        batch = {"image": pool[:b]}
        scorer.score(batch)                       # warm (still no compile)
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            scorer.score(batch)
            times.append(time.perf_counter() - t0)
        p50, p99 = pctl(times, 50), pctl(times, 99)
        rows.append({"family": family, "bucket": b,
                     "p50_ms": round(p50 * 1e3, 3),
                     "p99_ms": round(p99 * 1e3, 3),
                     "images_per_sec": round(b / max(p50, 1e-9), 1)})
        print(f"{family:10s} bucket={b:3d}  p50 {p50 * 1e3:7.2f} ms  "
              f"p99 {p99 * 1e3:7.2f} ms  {rows[-1]['images_per_sec']:9.1f} "
              "img/s")
    return rows


def arrival_sweep(family, servable, images, rates, duration_s, max_wait_s):
    """Open-loop fixed-rate arrivals through a live ScreeningService; each
    rate gets a fresh service so queue state never bleeds across rates.
    Returns (rows, trace_events) — the LAST rate's request trace."""
    rows, events = [], []
    for rate in rates:
        with ScreeningService(servable, image_shape=images.shape[1:],
                              precision="fp32", max_wait_s=max_wait_s,
                              max_queue=4096, trace=True) as svc:
            svc.score_one({"image": images[0]})   # warm the service path
            built = svc.scorer.n_compiles
            n = max(int(rate * duration_s), 8)
            period = 1.0 / rate
            reqs = []
            t_start = time.perf_counter()
            for i in range(n):
                target = t_start + i * period
                while time.perf_counter() < target:
                    pass                          # open-loop pacing
                reqs.append(svc.submit({"image": images[i % len(images)]}))
            for r in reqs:
                r.done.wait(60)
            elapsed = time.perf_counter() - t_start
            assert svc.scorer.n_compiles == built, \
                f"{family}: fresh compile during steady-state serving"
            lats = [r.lat["total_s"] for r in reqs]
            row = {"family": family, "rate_rps": rate,
                   "p50_ms": round(pctl(lats, 50) * 1e3, 3),
                   "p99_ms": round(pctl(lats, 99) * 1e3, 3),
                   "throughput_rps": round(n / elapsed, 1),
                   "batch_n_mean": round(svc.stats()["batch_n_mean"], 2)}
            rows.append(row)
            print(f"{family:10s} rate={rate:5g}/s  p50 {row['p50_ms']:7.2f}"
                  f" ms  p99 {row['p99_ms']:7.2f} ms  "
                  f"{row['throughput_rps']:7.1f} req/s  "
                  f"batch {row['batch_n_mean']:5.2f}")
            events = svc.trace_events()
    return rows, events


def check_against(baseline_path: str, fresh: dict,
                  max_regression: float = 0.2,
                  abs_slack_ms: float = 1.0) -> list[str]:
    """Gate p99 latency per (sweep, family, point) against a committed
    baseline: fail when fresh p99 exceeds committed * (1 + regression)
    + slack.  The absolute slack keeps sub-millisecond buckets from
    gating scheduler jitter."""
    with open(baseline_path) as f:
        committed = json.load(f)
    failures = []
    for sweep, keyf in (("batch_sweep", lambda r: r["bucket"]),
                        ("arrival_sweep", lambda r: r["rate_rps"])):
        base = {(r["family"], keyf(r)): r["p99_ms"]
                for r in committed.get(sweep, [])}
        for r in fresh.get(sweep, []):
            key = (r["family"], keyf(r))
            old = base.get(key)
            if old is None:
                continue
            ceil = old * (1.0 + max_regression) + abs_slack_ms
            status = "OK" if r["p99_ms"] <= ceil else "REGRESSED"
            print(f"  gate {sweep}:{key[0]}@{key[1]:<4g} committed "
                  f"{old:8.2f} ms  now {r['p99_ms']:8.2f} ms "
                  f"(ceil {ceil:.2f})  {status}")
            if r["p99_ms"] > ceil:
                failures.append(f"{sweep}:{key[0]}@{key[1]}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI (small ladder, short load)")
    ap.add_argument("--families", default=None)
    ap.add_argument("--buckets", default=None)
    ap.add_argument("--rates", default=None,
                    help="arrival rates (requests/s), comma-separated")
    ap.add_argument("--reps", type=int, default=None,
                    help="timed dispatches per bucket (batch sweep)")
    ap.add_argument("--duration", type=float, default=None,
                    help="seconds of load per arrival rate")
    ap.add_argument("--image-size", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--out", default=OUT)
    ap.add_argument("--trace", default=None,
                    help="write the merged request trace (Chrome JSON)")
    ap.add_argument("--check-against", default=None,
                    help="committed BENCH_serving.json to gate p99 "
                         "against (fail on >20%% + 1 ms regression)")
    args = ap.parse_args()

    families = (args.families.split(",") if args.families
                else list(FAMILIES))
    buckets = ([int(x) for x in args.buckets.split(",")] if args.buckets
               else ([1, 2, 4, 8] if args.smoke else [1, 2, 4, 8, 16, 32,
                                                      64]))
    rates = ([float(x) for x in args.rates.split(",")] if args.rates
             else ([50, 200] if args.smoke else [20, 50, 100, 200]))
    reps = args.reps or (20 if args.smoke else 100)
    duration = args.duration or (0.5 if args.smoke else 2.0)

    out = {"device": jax.devices()[0].device_kind,
           "families": families, "buckets": buckets, "rates": rates,
           "max_wait_ms": args.max_wait_ms,
           "batch_sweep": [], "arrival_sweep": [], "n_compiles": {}}
    all_traces = []
    for fi, family in enumerate(families):
        servable, images = trained_servable(family, args.image_size)
        scorer = BucketScorer(servable, image_shape=images.shape[1:],
                              buckets=buckets)
        built = scorer.n_compiles
        out["batch_sweep"] += batch_sweep(family, scorer, images, reps)
        assert scorer.n_compiles == built, \
            f"{family}: fresh compile during the timed batch sweep"
        out["n_compiles"][family] = built
        rows, events = arrival_sweep(family, servable, images, rates,
                                     duration, args.max_wait_ms * 1e-3)
        out["arrival_sweep"] += rows
        # one serving lane per family in the merged trace
        all_traces.append(merge_events(events,
                                       pid_offset=fi * 10 + PID_SERVING))

    out_dir = os.path.dirname(args.out)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")
    if args.trace:
        write_chrome_trace(merge_events(*all_traces), args.trace)
        print(f"wrote {args.trace}")

    if args.check_against:
        failures = check_against(args.check_against, out)
        if failures:
            print(f"FAIL: p99 latency regressed >20% (+1 ms slack) vs "
                  f"committed baseline for {failures}")
            sys.exit(1)
        print("serving p99 gate OK (within 20% + 1 ms of committed "
              "baseline)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
