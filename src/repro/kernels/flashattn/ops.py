"""Dispatching wrapper: Pallas flash attention with GQA folding.

Model layout (B, S, H, dh) + GQA (B, S, KV, dh) is folded to the
kernel's (B*H, S, dh) by repeating kv heads; the XLA fallback is the
chunked online-softmax attention in repro.models.attention.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention_pallas
from .ref import flash_attention_ref


def _fold_gqa(q, k, v):
    b, s, h, dh = q.shape
    n_kv = k.shape[2]
    g = h // n_kv
    kr = jnp.repeat(k, g, axis=2)
    vr = jnp.repeat(v, g, axis=2)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    return fold(q), fold(kr), fold(vr)


@partial(jax.jit, static_argnames=("causal", "use_pallas", "interpret"))
def flash_attention(q, k, v, *, causal=True, use_pallas=True,
                    interpret=None):
    """q (B,S,H,dh), k/v (B,S,KV,dh) -> (B,S,H,dh)."""
    b, s, h, dh = q.shape
    qf, kf, vf = _fold_gqa(q, k, v)
    fn = flash_attention_pallas if use_pallas else flash_attention_ref
    out = fn(qf, kf, vf, causal=causal) if not use_pallas else \
        flash_attention_pallas(qf, kf, vf, causal=causal,
                               interpret=interpret)
    return out.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
