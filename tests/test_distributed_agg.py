"""Aggregation-tier parity (repro.core.distributed): the hierarchical
reduce_scatter -> psum -> all_gather composition must equal the flat
psum and the reduce-to-root + broadcast port on a multi-axis
(pod, data, model) host mesh — exactly (integer-valued float frames stay
below 2^24, so every summation order is exact) — and the ``_pad_len``
divisibility contract that ``psum_scatter`` relies on must hold for the
meshes the repo builds."""
import os
import subprocess
import sys
import textwrap

from repro.core.engine import _pad_len


def test_pad_len_divisibility_contract():
    """hierarchical_allreduce psum_scatters over the flattened LOCAL
    tier, so the frame length must divide by every local-tier size that
    divides n_dev.  _pad_len rounds V+1 up to a multiple of n_dev —
    divisible by any factorization of the mesh into (pod, local) tiers
    — and never truncates."""
    for v in (60, 127, 4095, 70_000):
        for n_dev in (1, 2, 8, 256, 512):
            p = _pad_len(v, n_dev)
            assert p >= v + 1
            assert p % n_dev == 0
            # every local tier of a mesh with n_dev devices has a size
            # dividing n_dev: the scatter tiles evenly for all of them
            for local in (1, 2, 4, 8, 16, 64, 256):
                if n_dev % local == 0:
                    assert p % local == 0


_AGG_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from functools import partial
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import distributed as dist
    from repro.core.engine import _pad_len

    n_dev = 8
    v_pad = _pad_len(997, n_dev)          # awkward V, padded contract
    rng = np.random.default_rng(0)
    # integer-valued float frames (< 2^24): every reduction order exact
    frames = jnp.asarray(
        rng.integers(0, 1000, (n_dev, v_pad)).astype(np.float32))
    want = np.asarray(frames).sum(axis=0)

    meshes = [
        (("pod", "data", "model"), (2, 2, 2)),   # both tiers populated
        (("data", "model"), (2, 4)),             # no global tier
        (("pod", "data"), (4, 2)),               # thin local tier
    ]
    for axes, shape in meshes:
        mesh = jax.make_mesh(
            shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
        local_axes, global_axes = dist.sampler_axes(mesh)
        frame_spec = P(axes, None)

        @partial(jax.shard_map, mesh=mesh, in_specs=(frame_spec,),
                 out_specs=(P(), P(), P()), check_vma=False)
        def reduce_all(fr):
            x = fr[0]
            return (dist.hierarchical_allreduce(x, local_axes, global_axes),
                    dist.flat_allreduce(x, axes),
                    dist.reduce_to_root_and_broadcast(x, axes))

        h, f, r = jax.jit(reduce_all)(
            jax.device_put(frames, NamedSharding(mesh, frame_spec)))
        np.testing.assert_array_equal(np.asarray(h), want)
        np.testing.assert_array_equal(np.asarray(f), want)
        np.testing.assert_array_equal(np.asarray(r), want)
        # the scatter really tiled: local tier size divides the length
        local_size = 1
        for a in local_axes:
            local_size *= dict(zip(axes, shape))[a]
        assert v_pad % local_size == 0
        print(f"OK {axes}")

    # scalar frames (tau) take the flat path everywhere
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(("pod", "data", "model")),),
             out_specs=P(), check_vma=False)
    def tau_sum(t):
        return dist.flat_allreduce(t[0], ("pod", "data", "model"))

    taus = jnp.arange(8, dtype=jnp.int32)
    assert int(jax.jit(tau_sum)(taus)) == int(np.arange(8).sum())
    print("OK tau")
""")


def test_aggregation_tiers_agree_multi_axis_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _AGG_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert out.stdout.count("OK") == 4
