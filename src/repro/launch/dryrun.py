"""Multi-pod dry-run (deliverable e) + roofline source data (deliverable g).

For every (architecture × input shape × mesh) combination this lowers and
compiles the appropriate step function against pure ShapeDtypeStructs —
proving the distribution config is coherent without hardware — and records
memory analysis, HLO FLOPs/bytes and per-kind collective bytes into
``benchmarks/results/dryrun_<arch>_<shape>_<mesh>.json``.

  train_4k              -> SplitFedv3 train_step (the paper's technique;
                           virtual hospitals == data-parallel groups)
  prefill_32k           -> prefill_step (cache built in-program)
  decode_32k/long_500k  -> decode_step (one token vs a seq_len cache)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
      [--mesh single|multi|both] [--out benchmarks/results]
"""

import argparse
import json
import os
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim as O
from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import REGISTRY
from repro.launch import mesh as MESH
from repro.launch import specs as SPECS
from repro.launch.train import (get_axes_tree, init_sflv3_params,
                                make_sflv3_train_step)
from repro.models.transformer import TransformerLM
from repro.serving.engine import make_decode_step, make_prefill_step

# Per-chip hardware peaks the roofline terms divide by, keyed by
# ``jax.devices()[0].device_kind``.  Source for "TPU v5 lite" (TPU v5e):
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s (200e9 B/s) chip-to-chip interconnect.
# ``cpu_host`` is a nominal modern server (AVX-512 f32 FMA, dual-socket
# DDR5, 100 GbE); no device reports it, so CPU callers name it explicitly
# (``--hw cpu_host``).
V5E = "TPU v5 lite"
HW_TABLE = {
    V5E: {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 200e9},
    "cpu_host": {"peak_flops": 2e12, "hbm_bw": 200e9, "ici_bw": 12.5e9},
}


def default_hw() -> str:
    """The HW_TABLE key of the first local device; a device kind missing
    from the table is an error, never a silent default."""
    kind = jax.devices()[0].device_kind
    if kind not in HW_TABLE:
        raise KeyError(f"no peaks for device kind {kind!r} in HW_TABLE "
                       f"(known: {sorted(HW_TABLE)}); pass --hw explicitly")
    return kind

_DT_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
             "u32": 4, "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1,
             "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"(\w[\w\-]*)\[([0-9,]*)\][^=]*?\s(all-reduce|all-gather|reduce-scatter"
    r"|all-to-all|collective-permute)\(")
_SHAPE_RE = re.compile(r"(\w[\w\d]*)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Per-kind result-bytes of every collective op in the partitioned HLO."""
    out = {k: 0 for k in ("all-reduce", "all-gather", "reduce-scatter",
                          "all-to-all", "collective-permute")}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        for kind in out:
            tag = f" {kind}("
            if tag in line and "=" in line:
                lhs = line.split(tag)[0]
                # sum all result tensors (tuple results list each operand)
                rhs = lhs.split("=")[-1]
                for dt, dims in _SHAPE_RE.findall(rhs):
                    if dt not in _DT_BYTES:
                        continue
                    n = 1
                    for d in dims.split(","):
                        if d:
                            n *= int(d)
                    out[kind] += n * _DT_BYTES[dt]
                counts[kind] += 1
                break
    out["counts"] = counts
    return out


def _opt_shardings(opt_shapes, param_sh, mesh):
    return {"step": MESH.replicated(mesh),
            "mu": param_sh, "nu": param_sh}


def _apply_variant(cfg, variant: dict):
    import dataclasses
    fields = {k: v for k, v in (variant or {}).items()
              if k in {f.name for f in dataclasses.fields(cfg)}}
    return dataclasses.replace(cfg, **fields) if fields else cfg


def build_train(entry, shape_name, mesh, variant=None):
    variant = variant or {}
    cfg = _apply_variant(entry.config, variant)
    model = TransformerLM.build(cfg)
    n_clients = 1
    for a in MESH.dp_axes(mesh):
        n_clients *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    opt = O.adam(1e-4, state_dtype=jnp.bfloat16)
    step = make_sflv3_train_step(model, opt, n_clients,
                                 compress=variant.get("compress", False))

    param_shapes, axes = get_axes_tree(
        lambda k: init_sflv3_params(model, k, n_clients), jax.random.key(0))
    param_sh = MESH.tree_shardings(axes, param_shapes, mesh)
    opt_shapes = jax.eval_shape(opt.init, param_shapes)
    opt_sh = _opt_shardings(opt_shapes, param_sh, mesh)
    batch, batch_sh = SPECS.train_batch_specs(cfg, shape_name, mesh)
    args = (param_shapes, opt_shapes, batch)
    in_sh = (param_sh, opt_sh, batch_sh)
    out_sh = (param_sh, opt_sh, MESH.replicated(mesh))
    return step, args, in_sh, out_sh


def build_prefill(entry, shape_name, mesh, variant=None):
    cfg = _apply_variant(entry.config, variant or {})
    model = TransformerLM.build(cfg)
    s = INPUT_SHAPES[shape_name]["seq_len"]
    if cfg.frontend is not None:
        s += cfg.frontend_tokens
    step = make_prefill_step(model, max_len=s)
    param_shapes, axes = get_axes_tree(model.init, jax.random.key(0))
    param_sh = MESH.tree_shardings(axes, param_shapes, mesh)
    batch, batch_sh = SPECS.prefill_batch_specs(cfg, shape_name, mesh)
    _, cache_sh = SPECS.cache_specs(model, shape_name, mesh)
    b = INPUT_SHAPES[shape_name]["global_batch"]
    logits_sh = NamedSharding(mesh, P(MESH.dp_axes(mesh), "model"
                                      if cfg.vocab_size % 16 == 0 else None))
    args = (param_shapes, batch)
    return step, args, (param_sh, batch_sh), (logits_sh, cache_sh)


def build_decode(entry, shape_name, mesh, variant=None):
    cfg = _apply_variant(entry.config, variant or {})
    model = TransformerLM.build(cfg)
    step = make_decode_step(model)
    param_shapes, axes = get_axes_tree(model.init, jax.random.key(0))
    param_sh = MESH.tree_shardings(axes, param_shapes, mesh)
    cache_shapes, cache_sh = SPECS.cache_specs(model, shape_name, mesh)
    (tokens, positions), (tok_sh, pos_sh) = SPECS.decode_token_specs(
        shape_name, mesh)
    b = INPUT_SHAPES[shape_name]["global_batch"]
    dp = MESH.dp_axes(mesh)
    bspec = dp if b % SPECS._axes_size(mesh, dp) == 0 else None
    logits_sh = NamedSharding(mesh, P(bspec, "model"
                                      if cfg.vocab_size % 16 == 0 else None))
    args = (param_shapes, cache_shapes, tokens, positions)
    return step, args, (param_sh, cache_sh, tok_sh, pos_sh), \
        (logits_sh, cache_sh)


def run_combo(arch_id: str, shape_name: str, multi_pod: bool,
              save_hlo: str | None = None, variant: dict | None = None) -> dict:
    entry = REGISTRY[arch_id]
    mesh_name = "multi" if multi_pod else "single"
    rec = {"arch": arch_id, "shape": shape_name, "mesh": mesh_name,
           "status": "skipped", "notes": "", "variant": variant or {}}
    if shape_name not in entry.shapes:
        rec["notes"] = entry.skip_notes
        return rec
    mesh = MESH.make_production_mesh(multi_pod=multi_pod)
    kind = INPUT_SHAPES[shape_name]["kind"]
    t0 = time.time()
    try:
        with jax.default_device(jax.devices("cpu")[0]):
            if kind == "train":
                step, args, in_sh, out_sh = build_train(entry, shape_name,
                                                        mesh, variant)
            elif kind == "prefill":
                step, args, in_sh, out_sh = build_prefill(entry, shape_name,
                                                          mesh, variant)
            else:
                step, args, in_sh, out_sh = build_decode(entry, shape_name,
                                                         mesh, variant)
            with mesh:
                lowered = jax.jit(step, in_shardings=in_sh,
                                  out_shardings=out_sh).lower(*args)
                rec["lower_s"] = round(time.time() - t0, 1)
                t1 = time.time()
                compiled = lowered.compile()
                rec["compile_s"] = round(time.time() - t1, 1)

            mem = compiled.memory_analysis()
            if mem is not None:
                for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                          "output_size_in_bytes", "alias_size_in_bytes",
                          "generated_code_size_in_bytes"):
                    rec[k] = int(getattr(mem, k, 0) or 0)
            cost = compiled.cost_analysis()
            # raw cost_analysis counts while-loop bodies ONCE (verified);
            # hlo_analysis re-derives costs with trip-count multipliers.
            rec["hlo_flops_raw"] = float(cost.get("flops", 0.0))
            rec["hlo_bytes_raw"] = float(cost.get("bytes accessed", 0.0))
            txt = compiled.as_text()
            from repro.launch import hlo_analysis as HA
            ana = HA.analyze(txt)
            rec["hlo_flops"] = max(ana["flops"], rec["hlo_flops_raw"])
            rec["hlo_bytes"] = max(ana["hbm_bytes"], rec["hlo_bytes_raw"])
            rec["collectives"] = {**{k: int(v) for k, v in
                                     ana["collective_bytes"].items()},
                                  "counts": {k: int(v) for k, v in
                                             ana["collective_counts"].items()}}
            if save_hlo:
                with open(save_hlo, "w") as f:
                    f.write(txt)
            del txt
            rec["status"] = "ok"
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def roofline_terms(rec: dict, mesh_chips: int, hw=V5E) -> dict:
    """The three roofline terms in seconds (single-pod table; DESIGN.md §5).
    cost_analysis FLOPs/bytes are per-device program numbers on the
    partitioned module; collective bytes are per-device link traffic.
    ``hw``: an ``HW_TABLE`` key or peaks dict (default: TPU v5e, the
    chip the dry-run's production meshes describe)."""
    if isinstance(hw, str):
        hw = HW_TABLE[hw]
    coll = rec.get("collectives", {})
    coll_b = sum(v for k, v in coll.items() if k != "counts")
    t_compute = rec.get("hlo_flops", 0.0) / hw["peak_flops"]
    t_memory = rec.get("hlo_bytes", 0.0) / hw["hbm_bw"]
    t_coll = coll_b / hw["ici_bw"]
    dom = max((("compute", t_compute), ("memory", t_memory),
               ("collective", t_coll)), key=lambda kv: kv[1])[0]
    return {"t_compute": t_compute, "t_memory": t_memory,
            "t_collective": t_coll, "dominant": dom,
            "collective_bytes": coll_b}


def main():
    # the production meshes need 512 virtual host devices; set before the
    # first backend query (only this entry point wants them)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="benchmarks/results")
    ap.add_argument("--tag", default="", help="suffix for variant runs")
    ap.add_argument("--variant", default=None,
                    help='JSON config overrides, e.g. '
                         '\'{"vocab_pad_to": 256, "compress": true}\'')
    args = ap.parse_args()
    variant = json.loads(args.variant) if args.variant else None

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    archs = [args.arch] if args.arch else list(REGISTRY)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)

    for aid in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "multi" if mp else "single"
                tag = f"_{args.tag}" if args.tag else ""
                path = os.path.join(
                    args.out, f"dryrun_{aid}_{shape}_{mesh_name}{tag}.json")
                if os.path.exists(path):
                    rec = json.load(open(path))
                    if rec.get("status") == "ok":
                        print(f"[cached] {aid} {shape} {mesh_name}")
                        continue
                rec = run_combo(aid, shape, mp, variant=variant)
                if rec["status"] == "ok":
                    rec["roofline"] = roofline_terms(
                        rec, 512 if mp else 256)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                msg = rec.get("error", "") or rec.get("notes", "")
                print(f"[{rec['status']:7s}] {aid:24s} {shape:12s} "
                      f"{mesh_name:6s} {rec.get('total_s', 0):7.1f}s  {msg}",
                      flush=True)


if __name__ == "__main__":
    main()
