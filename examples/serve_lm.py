"""Batched LM serving demo: prefill + decode with KV caches.

Runs a small llama-style model, prefills a batch of prompts, then decodes
tokens autoregressively — the same serve_step the multi-pod dry-run lowers
for decode_32k/long_500k cells.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm


def main():
    enable_compile_cache()
    cfg = ModelConfig(name="serve-demo", n_layers=4, d_model=256, n_heads=8,
                      n_kv_heads=4, head_dim=32, d_ff=1024, vocab_size=8192,
                      pattern=(LayerSpec(),))
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, prompt_len, gen = 4, 32, 48
    cache_len = prompt_len + gen
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, prompt_len), 0,
                                 cfg.vocab_size)

    t0 = time.perf_counter()
    logits, caches = lm.prefill(cfg, params, prompts, cache_len=cache_len)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    step = jax.jit(lambda p, c, t, pos: lm.serve_step(cfg, p, c, t, pos))
    tok = jnp.argmax(logits, -1)[:, None]
    out = [tok]
    t0 = time.perf_counter()
    for i in range(gen - 1):
        logits, caches = step(params, caches, tok, prompt_len + i)
        tok = jnp.argmax(logits, -1)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0
    seqs = jnp.concatenate(out, axis=1)
    print(f"prefill {B}x{prompt_len} in {t_prefill*1e3:.1f}ms; "
          f"decoded {gen} tokens in {t_decode*1e3:.1f}ms "
          f"({B*gen/t_decode:.0f} tok/s incl. first-call jit)")
    print("sample:", seqs[0, :16].tolist())


if __name__ == "__main__":
    main()
