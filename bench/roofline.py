"""Roofline of the frontier expansion: the operations and bytes each route
cannot avoid per BFS level, and the chip's published peaks.

``run_adaptive`` leaves, on each call's ``run.end`` annotation, its
frontier route, sample width B, vertex and edge counts, the node-blocked
layout's blocking (0 off that route), the epochs' BFS expansions
(``bfs_levels``) and the edge blocks the node-blocked kernel streamed in
them (``nb_steps``).  :func:`frontier_seconds` turns one such record into
the least time the chip could take for those expansions: the larger of
operations over peak FLOP/s and bytes over peak HBM bytes/s.

Only work the route cannot avoid is counted, at the logical widths (B
lanes, not the 128 the chip pads them to), so the time is a lower bound:

* ``node_blocked``, per streamed edge block: its src and dst ids, the
  (block_v, B) dist and sigma source tiles, and the two one-hot matmuls
  (gather and scatter, 2 * block_v * block_e * B operations each, counted
  once although float32 at ``Precision.HIGHEST`` takes several bfloat16
  passes); per expansion, the (v_pad, B) contribution written once.
* ``ref``, per expansion: each edge's src and dst ids, the dist and sigma
  rows it gathers, one add per edge and sample, and the (V + 1, B)
  contribution written once.
"""
from __future__ import annotations

import bisect
import re

from bench import tracing

# Published peaks of one chip, keyed by jax ``Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bfloat16,
# 16 GB of HBM at 819 GB/s.
PEAKS = {"TPU v5 lite": {"flops": 197e12, "bytes": 819e9}}

_F32 = 4


def peaks(kind: str) -> dict:
    """The peaks of ``kind``; a device missing from the table is an
    error, not a default."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"add them to bench/roofline.py PEAKS")
    return PEAKS[kind]


def node_blocked(nb_steps: int, bfs_levels: int, *, n_nodes: int,
                 batch: int, block_v: int, block_e: int) -> tuple:
    """(operations, bytes) of ``bfs_levels`` expansions on the
    node-blocked kernel that streamed ``nb_steps`` edge blocks."""
    v_pad = -(-(n_nodes + 1) // block_v) * block_v
    flops = nb_steps * 2 * (2 * block_v * block_e * batch)
    per_step = _F32 * (2 * block_e + 2 * block_v * batch)
    return flops, nb_steps * per_step + bfs_levels * _F32 * v_pad * batch


def ref(bfs_levels: int, *, n_nodes: int, n_edges: int,
        batch: int) -> tuple:
    """(operations, bytes) of ``bfs_levels`` expansions on the XLA route
    over ``n_edges`` directed edges."""
    flops = bfs_levels * n_edges * batch
    per_level = _F32 * (2 * n_edges + 2 * n_edges * batch
                        + (n_nodes + 1) * batch)
    return flops, bfs_levels * per_level


def frontier_seconds(kind: str, end: dict):
    """Roofline seconds of the expansions one ``run.end`` record counts;
    ``None`` on a route with no count here (the flat and sharded
    kernels)."""
    common = dict(n_nodes=int(end["n_nodes"]), batch=int(end["batch_size"]))
    if end["route"] == "node_blocked":
        flops, nbytes = node_blocked(
            int(end["nb_steps"]), int(end["bfs_levels"]),
            block_v=int(end["block_v"]), block_e=int(end["block_e"]),
            **common)
    elif end["route"] == "ref":
        flops, nbytes = ref(int(end["bfs_levels"]),
                            n_edges=int(end["n_edges"]), **common)
    else:
        return None
    p = peaks(kind)
    return max(flops / p["flops"], nbytes / p["bytes"])


# A 2-D array in an XLA instruction's text: ``f32[261376,64]``.
_ARRAY = re.compile(r"[a-z]+\d*\[(\d+),(\d+)\]")


def frontier_op(hlo: str, n_edges: int, batch: int) -> bool:
    """Whether the XLA instruction ``hlo`` (an ``XLA Ops`` event's name:
    the instruction's text with its operands' shapes) belongs to the
    frontier expansion, by the arrays only the expansion touches: on the
    ``ref`` route the (edge slots, B) gather, mask and scatter; on the
    ``node_blocked`` route the kernel's (1, edge slots) id operands."""
    for rows, cols in _ARRAY.findall(hlo):
        rows, cols = int(rows), int(cols)
        if (rows >= n_edges and cols == batch) or \
                (rows == 1 and cols >= n_edges):
            return True
    return False


def _window(data):
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == tracing.WINDOW:
                        s = float(ev.start_ns)
                        return s, s + float(ev.duration_ns)
    return None


def frontier_device_s(data, *, n_edges: int, batch: int,
                      module: str = "jit_epoch_step"):
    """Device seconds, inside the traced window, of the frontier
    expansion's operations (:func:`frontier_op`) in the XLA module
    ``module``: their self time on the ``XLA Ops`` line, mean over the
    devices.  ``data`` is a ``jax.profiler.ProfileData`` (or anything with
    its ``planes``); ``None`` where no such operation ran."""
    window = _window(data)
    if window is None:
        return None
    w0, w1 = window
    per = []
    for plane in data.planes:
        if not tracing._DEVICE_PLANE.match(plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name not in (tracing._OPS_LINE, tracing._MODULES_LINE):
                continue
            for ev in line.events:
                s = max(float(ev.start_ns), w0)
                e = min(float(ev.start_ns) + float(ev.duration_ns), w1)
                if e <= s:
                    continue
                if line.name == tracing._MODULES_LINE:
                    if ev.name.split("(")[0] == module:
                        modules.append((s, e))
                else:
                    ops.append((s, e, ev.name))
        if not ops:
            continue
        modules.sort()
        starts = [m[0] for m in modules]

        def in_module(t):
            i = bisect.bisect_right(starts, t) - 1
            return i >= 0 and modules[i][1] >= t

        keyed = [(s, e, in_module(s) and frontier_op(name, n_edges, batch))
                 for s, e, name in ops]
        per.append(tracing._exclusive(keyed).get(True, 0.0))
    if not per or not any(per):
        return None
    return sum(per) / len(per) / 1e9


def read_frontier_device_s(trace_dir, **kw):
    """:func:`frontier_device_s` of the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    return frontier_device_s(ProfileData.from_file(
        tracing.find_xplane(str(trace_dir))), **kw)


def epoch_device_s(trace) -> float:
    """Device seconds of the epoch program (``jit_epoch_step``) in a
    traced window (``bench/tracing.py`` ``TraceSummary``), mean over the
    cell's devices."""
    return trace.op_share(lambda op: op.startswith("jit_epoch_step/")) \
        * trace.window_s()
