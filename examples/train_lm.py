"""End-to-end LM training driver with checkpointing.

Default config is CPU-feasible (~28M params, 150 steps in ~15 min on one
core); ``--big`` selects the ~100M/300-step variant for real hardware.
(The paper's kind — graph analytics — makes examples/quickstart.py and
examples/betweenness_scaling.py the primary end-to-end drivers; this
script is the generic-training counterpart.)

    PYTHONPATH=src python examples/train_lm.py [--steps N] [--big]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import CheckpointManager
from repro.data.pipeline import lm_batch_fn
from repro.models.common import active_mesh
from repro.models.transformer import TransformerConfig, init_params, lm_loss
from repro.launch.mesh import make_single_device_mesh
from repro.optim.adamw import AdamWConfig, init_state
from repro.train.step import make_train_step
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=150)
ap.add_argument("--big", action="store_true")
ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
args = ap.parse_args()

if args.big:   # ~100M params: 8L x d512 x ffn2048, 32k vocab
    cfg = TransformerConfig(
        name="lm-100m", n_layers=8, d_model=512, n_heads=8, n_kv_heads=4,
        d_ff=2048, vocab=32768, dtype=jnp.float32, attn_impl="dense",
        remat=False)
    if args.steps == 150:
        args.steps = 300
else:          # ~28M params, one-core-feasible
    cfg = TransformerConfig(
        name="lm-28m", n_layers=4, d_model=384, n_heads=6, n_kv_heads=3,
        d_ff=1536, vocab=16384, dtype=jnp.float32, attn_impl="dense",
        remat=False)
params = init_params(jax.random.PRNGKey(0), cfg)
n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
print(f"model: {n_params/1e6:.1f}M params")

opt = AdamWConfig(lr=3e-4)
state = init_state(params)
step_fn = jax.jit(make_train_step(lambda p, b: lm_loss(p, b, cfg), opt))
make_batch = lm_batch_fn(cfg.vocab, batch=8 if args.big else 2,
                         seq=256 if args.big else 128, seed=0)
mgr = CheckpointManager(args.ckpt, save_every=100)

mesh = make_single_device_mesh()
losses = []
t0 = time.perf_counter()
with active_mesh(mesh):
    for step in range(args.steps):
        batch = jax.tree.map(jnp.asarray, make_batch(step))
        params, state, metrics = step_fn(params, state, batch)
        losses.append(float(metrics["loss"]))
        if step % 25 == 0:
            print(f"step {step:4d} loss {losses[-1]:.4f} "
                  f"({(time.perf_counter()-t0):.0f}s)", flush=True)
        mgr.maybe_save(step + 1, (params, state))
mgr.wait()
print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
assert losses[-1] < losses[0] - 1.0, "model did not learn"
print("OK: loss decreased by", round(losses[0] - losses[-1], 2))
