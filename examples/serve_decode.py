"""Serving example: batched prefill + autoregressive decode with a KV cache,
on the SmolLM-family smoke config (and the mamba2 SSM family to show the
O(1)-state decode path).

  PYTHONPATH=src python examples/serve_decode.py
"""

import time

import jax

from repro.configs.registry import REGISTRY
from repro.models.transformer import TransformerLM
from repro.serving.engine import greedy_generate


def run(arch_id: str, batch=4, prompt_len=16, max_new=24):
    cfg = REGISTRY[arch_id].smoke
    model = TransformerLM.build(cfg)
    params = model.init_params(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (batch, prompt_len), 0,
                                cfg.vocab_size)
    t0 = time.time()
    out = greedy_generate(model, params, prompt, max_new=max_new,
                          max_len=prompt_len + max_new)
    dt = time.time() - t0
    print(f"[{arch_id}] generated {out.shape} tokens in {dt:.1f}s "
          f"({batch * max_new / dt:.1f} tok/s on CPU)")
    print("  first row:", out[0].tolist())


def main():
    run("smollm-135m")         # dense GQA decode
    run("mamba2-130m")         # SSM recurrent decode (O(1) state)
    run("zamba2-7b")           # hybrid: SSM + shared-attention KV cache


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
