"""frontier_roofline_share: the frontier expansion's roofline time over
its own device time, in %.  The roofline time is that of the expansions
the window's jobs counted (their ``run.end`` annotations: route, B,
blocking, ``bfs_levels``, ``nb_steps``), from the operations and bytes
their route cannot avoid at the chip's published peaks
(``bench/roofline.py``).  The device time is the self time, in the
window, of the epoch program's operations that touch the arrays only the
expansion touches (``roofline.frontier_op``: the ``ref`` route's
(edge slots, B) gather, mask and scatter).  A program that counts no
expansions leaves nothing to read."""
import jax

from bench import harness, phases, roofline


def read(run):
    ends = phases.named(run, "run.end")
    if not ends or any("bfs_levels" not in s for s in ends) \
            or run.get("trace") is None:
        return None
    kind = jax.devices()[0].device_kind
    parts = [roofline.frontier_seconds(kind, s) for s in ends]
    if None in parts:
        return None
    device_s = roofline.read_frontier_device_s(
        harness.TRACE_DIR, n_edges=int(ends[0]["n_edges"]),
        batch=int(ends[0]["batch_size"]))
    if not device_s:
        return None
    return 100.0 * sum(parts) / device_s
