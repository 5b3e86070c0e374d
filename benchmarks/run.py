"""Benchmark harness — one section per paper table + kernels + roofline.

Prints ``name,us_per_call,derived`` CSV rows:
  * kernel microbenchmarks: real jit wall-time per call (CPU interpret for
    Pallas; the number that matters on TPU comes from the roofline terms)
  * Table 2/3/4/5/6 analogues: derived from benchmarks/results/repro_*.json
    (produced by ``python -m benchmarks.repro_tables``)
  * roofline: dominant-term seconds per (arch, shape) from the dry-run

  PYTHONPATH=src python -m benchmarks.run
"""

from __future__ import annotations

import json
import os
import time


def _timeit(fn, *args, warmup=1, iters=5):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def bench_kernels(emit):
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    from repro.kernels.ssd_scan.ops import ssd
    from repro.kernels.act_compress.ops import quantize

    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    us = _timeit(flash_attention, q, k, v)
    ref = jax.jit(lambda a, b, c: flash_attention_ref(
        a.transpose(0, 2, 1, 3), b.transpose(0, 2, 1, 3),
        c.transpose(0, 2, 1, 3)))
    us_ref = _timeit(ref, q, k, v)
    emit("kernel/flash_attention_b1s256", us, f"ref_us={us_ref:.0f}")

    x = jax.random.normal(ks[0], (1, 256, 4, 32), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 256, 4)))
    A = jnp.exp(jax.random.normal(ks[2], (4,)) * 0.3)
    B = jax.random.normal(ks[3], (1, 256, 1, 32)) * 0.5
    C = jax.random.normal(ks[0], (1, 256, 1, 32)) * 0.5
    us = _timeit(lambda *a: ssd(*a, 64), x, dt, A, B, C)
    emit("kernel/ssd_scan_l256", us, "chunk=64")

    xa = jax.random.normal(ks[1], (512, 1024), jnp.bfloat16)
    us = _timeit(quantize, xa)
    emit("kernel/act_compress_512x1024", us,
         f"ratio={xa.nbytes / (512 * 1024 + 512 * 4):.2f}x")


def bench_tables(emit, results="benchmarks/results"):
    for arch in ("densenet-mini", "unet-mini"):
        path = os.path.join(results, f"repro_{arch}.json")
        if not os.path.exists(path):
            emit(f"table2/{arch}", 0, "MISSING (run benchmarks.repro_tables)")
            continue
        rows = json.load(open(path))
        for r in rows:
            emit(f"table2/{arch}/{r['label']}", r["wall_s"] * 1e6,
                 f"auroc={r['auroc']};auprc={r['auprc']};f1={r['f1']};"
                 f"kappa={r['kappa']}")
            emit(f"table3/{arch}/{r['label']}",
                 r["epoch_time_s"] * 1e6, "epoch_time")
            emit(f"table4/{arch}/{r['label']}", 0,
                 f"comm_gb={r['comm_gb']}")
            emit(f"table56/{arch}/{r['label']}", 0,
                 f"server_tf={r['server_tflops']};"
                 f"client_tf={r['avg_client_tflops']};"
                 f"avg_mf={r['averaging_mflops']}")


def bench_roofline(emit, results="benchmarks/results"):
    try:
        from benchmarks.roofline import roofline_table
    except Exception as e:
        emit("roofline", 0, f"ERROR {e}")
        return
    for r in roofline_table(results):
        if r.get("status") != "ok":
            continue
        dom_t = {"compute": r["t_compute_s"], "memory": r["t_memory_s"],
                 "collective": r["t_collective_s"]}[r["dominant"]]
        emit(f"roofline/{r['arch']}/{r['shape']}", dom_t * 1e6,
             f"dominant={r['dominant']};useful={r['useful_ratio']:.3f}")


def main() -> None:
    rows = []

    def emit(name, us, derived=""):
        rows.append((name, us, derived))
        print(f"{name},{us:.1f},{derived}", flush=True)

    print("name,us_per_call,derived")
    bench_kernels(emit)
    bench_tables(emit)
    bench_roofline(emit)
    print(f"# {len(rows)} rows", flush=True)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
