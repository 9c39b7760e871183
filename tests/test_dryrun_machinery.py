"""Validate the dry-run cost-extrapolation methodology itself:
on a small config, the (k, c)-extrapolated totals must equal a fully
unrolled exact compile."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.launch.dryrun import _compile_once, _lin
    from repro.models import registry
    from repro.models.common import LoopConfig
    from repro.models.transformer import TransformerConfig

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    axes = tuple(mesh.axis_names)
    arch = registry.get("llama3.2-3b")

    import repro.configs._families as fam
    fam.LM_SHAPES["train_4k"] = dict(seq=512, batch=8)  # small twin
    cfg = TransformerConfig(
        name="t", n_layers=6, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab=512, attn_chunk=128, dtype=jnp.float32,
        remat=False, train_microbatch=1)

    # exact: fully unrolled production loops (k=6 groups, c=4 chunks)
    exact, _ = _compile_once(arch, "train_4k", mesh, axes,
                             LoopConfig(unroll=True), config=cfg)
    # extrapolated from the 3 tiny measurement compiles
    f11, _ = _compile_once(arch, "train_4k", mesh, axes,
                           LoopConfig(1, 1, True, False), config=cfg)
    f12, _ = _compile_once(arch, "train_4k", mesh, axes,
                           LoopConfig(1, 2, True, False), config=cfg)
    f21, _ = _compile_once(arch, "train_4k", mesh, axes,
                           LoopConfig(2, 1, True, False), config=cfg)
    K, C = 6, 4
    pred_flops = (f11["flops"] + (K - 1) * (f21["flops"] - f11["flops"])
                  + (K * C - K) * (f12["flops"] - f11["flops"]))
    err = abs(pred_flops - exact["flops"]) / exact["flops"]
    print(f"flops exact {exact['flops']:.4e} pred {pred_flops:.4e} "
          f"relerr {err:.4f}")
    assert err < 0.02, err
    pred_bytes = (f11["bytes"] + (K - 1) * (f21["bytes"] - f11["bytes"])
                  + (K * C - K) * (f12["bytes"] - f11["bytes"]))
    berr = abs(pred_bytes - exact["bytes"]) / exact["bytes"]
    print(f"bytes relerr {berr:.4f}")
    assert berr < 0.05, berr
    print("EXTRAPOLATION OK")
""")


def test_kc_extrapolation_matches_exact_unroll():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    assert "EXTRAPOLATION OK" in out.stdout
