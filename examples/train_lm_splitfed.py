"""End-to-end driver (deliverable b): train a ~small LM with SplitFedv3 for a
few hundred steps on a synthetic Markov token stream and watch the loss fall.

The same ``make_sflv3_train_step`` lowers onto the 256-chip production mesh
in the dry-run; here it runs on CPU with 4 virtual hospitals.

  PYTHONPATH=src python examples/train_lm_splitfed.py [--steps 200]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim as O
from repro.data.synthetic import lm_clients
from repro.launch.train import init_sflv3_params, make_sflv3_train_step
from repro.models.transformer import ModelConfig, TransformerLM
from repro.train import checkpoint


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)    # per client
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default="/tmp/repro_sflv3_lm.msgpack")
    args = ap.parse_args()

    cfg = ModelConfig(name="quick-lm", arch_type="dense", n_layers=4,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab_size=256, cut_layer=1, remat=False,
                      compute_dtype=jnp.float32)
    model = TransformerLM.build(cfg)
    params, _ = init_sflv3_params(model, jax.random.key(0), args.clients)
    opt = O.adam(O.wsd(3e-3, warmup=20, stable=args.steps // 2,
                       decay=args.steps // 2))
    opt_state = opt.init(params)
    step = jax.jit(make_sflv3_train_step(model, opt, args.clients))

    data = lm_clients(seed=0, vocab=cfg.vocab_size,
                      n_clients=args.clients, seqs_per_client=256,
                      seq_len=args.seq + 1)
    rng = np.random.default_rng(0)

    t0 = time.time()
    for i in range(args.steps):
        toks = np.stack([d[rng.integers(0, len(d), args.batch)]
                         for d in data])            # (C, B, S+1)
        batch = {"tokens": jnp.asarray(
            toks.reshape(args.clients * args.batch, args.seq + 1))}
        params, opt_state, loss = step(params, opt_state, batch)
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss={float(loss):.4f}  "
                  f"({time.time() - t0:.0f}s)", flush=True)
    checkpoint.save(args.ckpt, params)
    print(f"saved checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
