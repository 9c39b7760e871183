# Pallas TPU kernels for the compute hot-spots:
#   frontier  — BFS frontier expansion (the paper's per-sample inner loop)
#   segsum    — fused gather + segment-sum (GNN aggregation / EmbeddingBag)
#   stopcheck — fused KADABRA f/g stopping-condition evaluation
#   flashattn — fused causal attention (the LM memory-bound hot spot
#               identified by DESIGN.md §Perf cell 1)
# Each subpackage ships kernel.py (pl.pallas_call + BlockSpec), ops.py
# (jit'd dispatching wrapper) and ref.py (pure-jnp oracle).

import jax


def resolve_interpret(interpret):
    """A kernel entry point's ``interpret`` flag: ``None`` compiles the
    Pallas kernel on a TPU backend and interprets it on any other, so a
    caller on the chip never lands in interpret mode by accident."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
