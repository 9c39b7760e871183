"""Serving example: batched prefill + decode with a KV cache, including a
sliding-window (gemma3-style) layer pattern.

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.transformer import (TransformerConfig, decode_step,
                                      init_params, prefill_step)
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

cfg = TransformerConfig(
    name="serve-demo", n_layers=6, d_model=256, n_heads=8, n_kv_heads=4,
    d_ff=1024, vocab=32768, layer_pattern=("local", "local", "global"),
    window=64, dtype=jnp.float32, attn_impl="dense", remat=False)
params = init_params(jax.random.PRNGKey(0), cfg)

B, prompt_len, gen_len = 4, 96, 32
prompt = jax.random.randint(jax.random.PRNGKey(1), (B, prompt_len), 0,
                            cfg.vocab)

prefill = jax.jit(lambda p, t: prefill_step(p, t, cfg))
decode = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))

t0 = time.perf_counter()
logits, cache = prefill(params, prompt)
# grow the cache for generation
cache = {"k": jnp.pad(cache["k"], ((0, 0), (0, 0), (0, gen_len), (0, 0),
                                   (0, 0))),
         "v": jnp.pad(cache["v"], ((0, 0), (0, 0), (0, gen_len), (0, 0),
                                   (0, 0))),
         "len": cache["len"]}
print(f"prefill {B}x{prompt_len}: {(time.perf_counter()-t0)*1e3:.0f}ms")

tokens = jnp.argmax(logits, -1)[:, None]
out = [tokens]
t0 = time.perf_counter()
for i in range(gen_len - 1):
    logits, cache = decode(params, cache, tokens)
    tokens = jnp.argmax(logits, -1)[:, None]
    out.append(tokens)
gen = jnp.concatenate(out, axis=1)
dt = time.perf_counter() - t0
print(f"decoded {gen_len-1} tokens/seq x {B} seqs: "
      f"{dt/(gen_len-1)*1e3:.1f} ms/token")
assert np.isfinite(np.asarray(logits)).all()
print("generated token ids (seq 0):", np.asarray(gen[0][:16]))
print("OK")
