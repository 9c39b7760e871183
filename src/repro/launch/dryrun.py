import os
# 512 fake host devices, on the CPU: never the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces:
  * the FULL compile (production loop structure, lax.scan over layer
    groups) -> memory_analysis() proves the program fits the 16 GiB HBM;
  * 1-3 tiny MEASUREMENT compiles (unrolled, truncated loop counts) from
    which exact HLO totals are extrapolated (cost_analysis counts a scan
    body once; DESIGN.md §Roofline methodology):

      basis "exact": F_total = F(full)                      [GNN, recsys]
      basis "k"    : F(k)   = A + kB      -> 2 compiles     [LM decode]
      basis "kc"   : F(k,c) = A + k(B+cC) -> 3 compiles     [LM train/prefill]
    + one remainder compile when the layer pattern does not divide the
      depth (gemma3: 62 = 10x6 + 2).

Collective bytes are parsed from the post-SPMD optimized HLO of the same
measurement compiles, so they extrapolate with the same basis.

Results land in experiments/dryrun/<arch>__<cell>__<mesh>.json; the
roofline report (benchmarks/roofline.py) consumes them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.models.common import LoopConfig

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# one HLO instruction: "%name = TYPE op-name(..." — TYPE may be a tuple
_INSTR_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"((?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?)\((.*)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# replica_groups appear as explicit lists {{0,1,..},..} or iota
# [G,S]<=[N] (G groups of S members)
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]*)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(rest_of_line: str) -> int:
    m = _GROUPS_IOTA_RE.search(rest_of_line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(rest_of_line)
    if m:
        return m.group(1).count(",") + 1
    return 1


def collective_stats(hlo_text: str) -> dict:
    """Per-device *ring-traffic* bytes per collective kind.

    Output-shape proxy with the per-kind correction:
      all-gather      : output is the gathered (full) tensor -> bytes moved
                        per device ~ output * (g-1)/g ~ output
      reduce-scatter  : output is the 1/g shard; bytes moved ~ input ~
                        output * group_size
      all-reduce      : payload = shape; ring send+recv -> weighted 2x in
                        the roofline term (benchmarks/roofline.py)
      all-to-all /
      collective-permute: output-sized
    '-done' ops are skipped so async pairs count once."""
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for m in _INSTR_RE.finditer(hlo_text):
        type_str, opname, rest = m.group(1), m.group(2), m.group(3)
        base = opname.replace("-start", "")
        nbytes = _shape_bytes(type_str)
        if base == "reduce-scatter":
            nbytes *= _group_size(rest)
        out[base] += nbytes
        counts[base] += 1
    return {"bytes": out, "counts": counts}


# --------------------------------------------------------------------------
# Per-computation collective accounting (cond-branch attribution)
#
# ``collective_stats`` sums over the WHOLE module text, so a ``lax.cond``
# contributes the collectives of BOTH its arms even though a device
# executes exactly one per invocation.  The helpers below split the HLO
# into named computations, walk the call graph (kWhile / kConditional /
# kCall / fusions), and attribute transitive collective bytes to each
# branch of a conditional — letting callers subtract the branch NOT
# taken instead of reporting the double-counted module total.
# --------------------------------------------------------------------------

# "%name (params...) -> result {"  — computation header (ENTRY or not);
# params may hold nested parens (tuple types), hence the greedy middle
_COMP_HEADER_RE = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
# call-graph edges carried by instruction attributes
_CALLS_RE = re.compile(
    r"(?:to_apply|condition|body|calls|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def split_computations(hlo_text: str) -> dict:
    """Map computation name -> its body text (header line included).

    HLO computations never nest, but instruction lines carry inline
    balanced braces (``replica_groups={{...}}``, ``metadata={...}``), so
    a running per-line brace depth cleanly finds each closing ``}``."""
    comps, name, depth, buf = {}, None, 0, []
    for line in hlo_text.splitlines():
        if name is None:
            m = _COMP_HEADER_RE.match(line)
            if m:
                name, depth, buf = m.group(1), 0, []
        if name is not None:
            buf.append(line)
            depth += line.count("{") - line.count("}")
            if depth <= 0:
                comps[name] = "\n".join(buf)
                name = None
    return comps


def _computation_callees(body: str) -> list:
    out = [m.group(1) for m in _CALLS_RE.finditer(body)]
    for m in _BRANCHES_RE.finditer(body):
        out.extend(p.strip().lstrip("%")
                   for p in m.group(1).split(",") if p.strip())
    return out


def _reachable(comps: dict, root: str) -> set:
    seen, stack = set(), [root]
    while stack:
        name = stack.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        stack.extend(_computation_callees(comps[name]))
    return seen


def _transitive_stats(comps: dict, root: str) -> dict:
    """Collective bytes/counts of ``root`` plus everything it can call.

    Each reachable computation is counted ONCE — the same
    text-appears-once semantics as ``collective_stats`` over the module,
    so branch totals subtract cleanly from the module total."""
    total = {"bytes": {k: 0.0 for k in _COLLECTIVES},
             "counts": {k: 0 for k in _COLLECTIVES}}
    for name in _reachable(comps, root):
        st = collective_stats(comps[name])
        for k in _COLLECTIVES:
            total["bytes"][k] += st["bytes"][k]
            total["counts"][k] += st["counts"][k]
    return total


def cond_branch_collective_stats(hlo_text: str) -> list:
    """Per-branch transitive collective stats for every HLO conditional.

    Returns one entry per ``conditional(...)`` instruction:
    ``{"branches": [{"computation": name, "bytes": {...},
    "counts": {...}}, ...]}``, ordered as the branch list appears."""
    comps = split_computations(hlo_text)
    out = []
    for body in comps.values():
        for line in body.splitlines():
            if " conditional(" not in line:
                continue
            names = []
            m = _BRANCHES_RE.search(line)
            if m:
                names = [p.strip().lstrip("%")
                         for p in m.group(1).split(",") if p.strip()]
            else:
                attrs = dict(
                    (a, v) for a, v in re.findall(
                        r"(true_computation|false_computation)=%?([\w.\-]+)",
                        line))
                if "true_computation" in attrs:
                    # report [false, true] = HLO branch-index order
                    names = [attrs.get("false_computation"),
                             attrs.get("true_computation")]
                    names = [n for n in names if n]
            if not names:
                continue
            out.append({"branches": [
                dict(computation=n, **_transitive_stats(comps, n))
                for n in names]})
    return out


def exchange_branch_accounting(hlo_text: str) -> "dict | None":
    """Attribute the frontier-exchange ``lax.cond``'s all-gather bytes.

    Finds the conditional moving the most all-gather traffic across its
    branches (the per-level sparse/dense protocol switch — the only
    data-dependent all-gather in the partitioned epoch), labels the
    heavier branch ``dense`` and the lighter ``sparse``, and returns
    module-total all-gather bytes corrected to each taken-branch
    hypothesis.  None when no conditional carries an all-gather."""
    conds = cond_branch_collective_stats(hlo_text)
    best, best_ag = None, 0.0
    for c in conds:
        ag = sum(b["bytes"]["all-gather"] for b in c["branches"])
        if ag > best_ag:
            best, best_ag = c, ag
    if best is None or len(best["branches"]) < 2:
        return None
    ranked = sorted(best["branches"],
                    key=lambda b: b["bytes"]["all-gather"])
    sparse, dense = ranked[0], ranked[-1]
    raw = collective_stats(hlo_text)["bytes"]["all-gather"]
    return {
        "dense_branch": {"computation": dense["computation"],
                         "all_gather_bytes":
                             float(dense["bytes"]["all-gather"])},
        "sparse_branch": {"computation": sparse["computation"],
                          "all_gather_bytes":
                              float(sparse["bytes"]["all-gather"])},
        "module_all_gather_bytes_raw": float(raw),
        # module total with the NOT-taken arm's bytes removed — what a
        # device actually moves under each protocol hypothesis
        "module_all_gather_bytes_if_sparse_taken":
            float(raw - dense["bytes"]["all-gather"]),
        "module_all_gather_bytes_if_dense_taken":
            float(raw - sparse["bytes"]["all-gather"]),
    }


# HLO while instruction: "%name = TYPE while(%operand), condition=..."
_WHILE_RE = re.compile(r"=\s*\S+\s+while\(")


def while_loop_stats(hlo_text: str) -> dict:
    """Count HLO ``while`` instructions per computation and in total.

    The estimator-substrate acceptance check rests on this: a
    multi-metric epoch step must lower to the SAME number of while loops
    (i.e. the same single BFS per sampling round — diameter phase,
    SSSP sweep, backward walk) as a single-metric step on the same
    stream, because extra estimators only add fold arithmetic, never
    extra traversals.  Counted on the post-optimization module text, so
    loops DCE'd or fused away do not inflate the number."""
    per_comp = {}
    for name, body in split_computations(hlo_text).items():
        n = len(_WHILE_RE.findall(body))
        if n:
            per_comp[name] = n
    return {"while_total": sum(per_comp.values()),
            "while_by_computation": per_comp}


def _to_shardings(mesh, tree):
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def _jit_cell(built, mesh):
    return jax.jit(built.fn,
                   in_shardings=_to_shardings(mesh, built.in_shardings),
                   donate_argnums=built.donate)


def _cost_analysis(compiled) -> dict:
    """Normalize Compiled.cost_analysis() across JAX versions (older
    releases return a one-element list of dicts, newer ones a dict)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca or {}


def _compile_once(arch, cell_name, mesh, mesh_axes, loop, config=None):
    from repro.models.common import active_mesh
    built = arch.build(cell_name, config=config, loop=loop,
                       mesh_axes=mesh_axes)
    with active_mesh(mesh):
        t0 = time.time()
        lowered = _jit_cell(built, mesh).lower(*built.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ca = _cost_analysis(compiled)
    stats = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "t_lower_s": t_lower,
        "t_compile_s": t_compile,
    }
    stats["collectives"] = collective_stats(compiled.as_text())
    ma = compiled.memory_analysis()
    if ma is not None:
        stats["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
        }
    return stats, built


def _lin(d):
    return {**{k: d[k] for k in ("flops", "bytes", "transcendentals")},
            "coll": dict(d["collectives"]["bytes"])}


def _combine(terms, coeffs):
    """Linear combination of measurement stats dicts."""
    out = {"flops": 0.0, "bytes": 0.0, "transcendentals": 0.0,
           "coll": {k: 0.0 for k in _COLLECTIVES}}
    for t, c in zip(terms, coeffs):
        out["flops"] += c * t["flops"]
        out["bytes"] += c * t["bytes"]
        out["transcendentals"] += c * t["transcendentals"]
        for k in _COLLECTIVES:
            out["coll"][k] += c * t["coll"][k]
    return out


# --- perf-variant catalogue (hillclimb; DESIGN.md §Perf) ------------
# each entry: config transform applied before building the cell
def _variant_cfg(cfg, variant: str):
    if variant == "base" or variant is None:
        return cfg
    if variant == "fsdp":
        # pure data parallelism over every mesh axis + ZeRO-3 weights
        return dataclasses.replace(cfg, param_sharding="fsdp",
                                   batch_axes=("pod", "data", "model"),
                                   train_microbatch=1)
    if variant.startswith("micro"):
        return dataclasses.replace(cfg,
                                   train_microbatch=int(variant[5:]))
    if variant == "fsdp_micro2":
        return dataclasses.replace(cfg, param_sharding="fsdp",
                                   batch_axes=("pod", "data", "model"),
                                   train_microbatch=2)
    if variant == "noremat":
        return dataclasses.replace(cfg, remat=False)
    if variant == "nodes_rep":
        return dataclasses.replace(cfg, node_sharding="replicated")
    if variant == "agg_bf16":
        return dataclasses.replace(cfg, agg_dtype="bf16")
    if variant == "partitioned":
        return dataclasses.replace(cfg, partitioned=True)
    if variant == "trapezoid":
        return dataclasses.replace(cfg, attn_trapezoid=True)
    if variant == "fsdp_trap":
        return dataclasses.replace(cfg, param_sharding="fsdp",
                                   batch_axes=("pod", "data", "model"),
                                   train_microbatch=1, attn_trapezoid=True)
    if variant == "fsdp_trap_sel":
        return dataclasses.replace(cfg, param_sharding="fsdp",
                                   batch_axes=("pod", "data", "model"),
                                   train_microbatch=1, attn_trapezoid=True,
                                   remat_policy="save_proj")
    if variant == "fsdp_trap_sel_closs":
        return dataclasses.replace(cfg, param_sharding="fsdp",
                                   batch_axes=("pod", "data", "model"),
                                   train_microbatch=1, attn_trapezoid=True,
                                   remat_policy="save_proj",
                                   loss_chunk=512)
    if variant == "fsdp_trap_sel2":
        return dataclasses.replace(cfg, param_sharding="fsdp",
                                   batch_axes=("pod", "data", "model"),
                                   train_microbatch=1, attn_trapezoid=True,
                                   remat_policy="save_qkv")
    if variant == "fsdp_trap_noremat":
        return dataclasses.replace(cfg, param_sharding="fsdp",
                                   batch_axes=("pod", "data", "model"),
                                   train_microbatch=1, attn_trapezoid=True,
                                   remat=False)
    if variant == "chunk2048":
        return dataclasses.replace(cfg, attn_chunk=2048)
    raise ValueError(f"unknown variant {variant}")


def run_cell(arch_id: str, cell_name: str, mesh_name: str,
             out_dir: str = OUT_DIR, variant: str = None) -> dict:
    arch = registry.get(arch_id)
    cell = arch.cells[cell_name]
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    mesh_axes = tuple(mesh.axis_names)
    n_chips = int(np.prod(mesh.devices.shape))

    record = {
        "arch": arch_id, "cell": cell_name, "mesh": mesh_name,
        "chips": n_chips, "family": arch.family, "basis": cell.basis,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    if variant:
        record["variant"] = variant
    if cell.skip:
        record["skipped"] = cell.skip
        _write(record, out_dir)
        return record

    cfg = _variant_cfg(arch.make_config(), variant)

    # ---- full compile (memory truth + production collective schedule) --
    full_stats, built = _compile_once(arch, cell_name, mesh, mesh_axes,
                                      LoopConfig(), config=cfg)
    record["full"] = full_stats
    K, C = built.n_groups, built.n_chunks

    # ---- measurement compiles + extrapolation ---------------------------
    if cell.basis == "exact":
        record["extrapolated"] = _lin(full_stats)
        record["measure_compiles"] = 0
    elif cell.basis == "k":
        f1, _ = _compile_once(arch, cell_name, mesh, mesh_axes,
                              LoopConfig(layer_groups=1, unroll=True,
                                         remainder=False), config=cfg)
        f2, _ = _compile_once(arch, cell_name, mesh, mesh_axes,
                              LoopConfig(layer_groups=2, unroll=True,
                                         remainder=False), config=cfg)
        a, b = _lin(f1), _lin(f2)
        # F(k) = A + kB ; total = A + K*B (+ remainder)
        total = _combine([a, b], [2.0 - K, K - 1.0])
        record["measure_compiles"] = 2
        total = _add_remainder(arch, cell_name, mesh, mesh_axes, a, total,
                               record, chunks=None)
        record["extrapolated"] = total
    elif getattr(cfg, "attn_trapezoid", False):
        # "kct": per-layer cost = B + cC + T(c)D, T(c) = c(c+1)/2
        # (the trapezoid schedule makes global layers quadratic in the
        # chunk count and window layers linear) -> 4 measurement points
        fs = {}
        for (kk, cc) in [(1, 1), (1, 2), (1, 4), (2, 1)]:
            f, _ = _compile_once(
                arch, cell_name, mesh, mesh_axes,
                LoopConfig(layer_groups=kk, attn_chunks=cc, unroll=True,
                           remainder=False), config=cfg)
            fs[(kk, cc)] = _lin(f)
        # D = (F14 - 3 F12 + 2 F11)/3 ; C = (F12 - F11) - 3D
        # B+C+D = F21 - F11 ; A = F11 - (B + C + D)
        # total = A + K(B + cC + T(c)D)
        Tc = C * (C + 1) / 2.0
        # symbolic solve:
        #   D_ = (f14 - 3 f12 + 2 f11)/3
        #   C_ = f12 - f11 - 3 D_
        #   BCD = f21 - f11          (= B + C + D at k-slope)
        #   A_ = f11 - BCD
        #   total = A_ + K*(BCD - C_ - D_ + C*C_ + Tc*D_)
        f11, f12, f14, f21 = (fs[(1, 1)], fs[(1, 2)], fs[(1, 4)],
                              fs[(2, 1)])
        D_ = _combine([f14, f12, f11], [1 / 3, -1.0, 2 / 3])
        C_ = _combine([f12, f11, D_], [1.0, -1.0, -3.0])
        BCD = _combine([f21, f11], [1.0, -1.0])
        A_ = _combine([f11, BCD], [1.0, -1.0])
        total = _combine([A_, BCD, C_, D_],
                         [1.0, K, K * (C - 1.0), K * (Tc - 1.0)])
        record["measure_compiles"] = 4
        total = _add_remainder(arch, cell_name, mesh, mesh_axes, f11,
                               total, record, chunks=None, config=cfg)
        record["extrapolated"] = total
    else:  # "kc"
        f11, _ = _compile_once(arch, cell_name, mesh, mesh_axes,
                               LoopConfig(layer_groups=1, attn_chunks=1,
                                          unroll=True, remainder=False),
                               config=cfg)
        f12, _ = _compile_once(arch, cell_name, mesh, mesh_axes,
                               LoopConfig(layer_groups=1, attn_chunks=2,
                                          unroll=True, remainder=False),
                               config=cfg)
        f21, _ = _compile_once(arch, cell_name, mesh, mesh_axes,
                               LoopConfig(layer_groups=2, attn_chunks=1,
                                          unroll=True, remainder=False),
                               config=cfg)
        a11, a12, a21 = _lin(f11), _lin(f12), _lin(f21)
        # F(k,c) = A + k(B + cC)
        # C = F12 - F11 ; B + C = F21 - F11 ... solve per component
        #   total = A + K*B + K*Cn*C  with Cn = real chunk count
        # A = F11 - (B + C); B = (F21 - F11) - C; C = F12 - F11
        #   => total = F11 + (K-1)(F21-F11) + (K*Cn - K)(F12 - F11)
        total = _combine([a11, a21, a12],
                         [1.0 - (K - 1.0) - (K * C - K),
                          K - 1.0, K * C - K])
        record["measure_compiles"] = 3
        total = _add_remainder(arch, cell_name, mesh, mesh_axes, a11,
                               total, record, chunks=1, config=cfg)
        record["extrapolated"] = total

    # analytic model flops for the useful-compute ratio
    if arch.model_flops is not None:
        record["model_flops"] = float(arch.model_flops(cfg, cell_name))
    _write(record, out_dir)
    return record


def _add_remainder(arch, cell_name, mesh, mesh_axes, base_lin, total,
                   record, chunks, config=None):
    """Remainder layers (pattern does not divide depth): one extra compile
    F(k=1, rem=True) - F(k=1, rem=False) added verbatim.  The remainder's
    own attention-chunk scaling is folded in by measuring it at the real
    chunk count via the production (non-truncated) chunks."""
    cfg = config if config is not None else arch.make_config()
    n_rem = getattr(cfg, "n_remainder", 0)
    if not n_rem:
        return total
    loop = LoopConfig(layer_groups=1, attn_chunks=None, unroll=True,
                      remainder=True)
    f_rem, _ = _compile_once(arch, cell_name, mesh, mesh_axes, loop,
                             config=cfg)
    loop0 = LoopConfig(layer_groups=1, attn_chunks=None, unroll=True,
                       remainder=False)
    f_no, _ = _compile_once(arch, cell_name, mesh, mesh_axes, loop0,
                            config=cfg)
    rem = _combine([_lin(f_rem), _lin(f_no)], [1.0, -1.0])
    record["measure_compiles"] = record.get("measure_compiles", 0) + 2
    return _combine([total, rem], [1.0, 1.0])


def _write(record, out_dir):
    os.makedirs(out_dir, exist_ok=True)
    name = "{}__{}__{}".format(
        record["arch"].replace("/", "_"), record["cell"], record["mesh"])
    if record.get("variant"):
        name += "__" + record["variant"]
    name += ".json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(record, f, indent=1)
    print(f"[dryrun] wrote {name}", flush=True)


def run_betweenness(mesh_name: str, aggregation: str,
                    rmat_scale: int = 22, out_dir: str = OUT_DIR,
                    n0: int = 1, batch_size: int | None = None,
                    partitioned: bool = False,
                    metric: str = "betweenness",
                    stream: str | None = None) -> dict:
    """Lower + compile one SPMD adaptive-sampling epoch (the paper's own
    workload) on the production mesh, with abstract graph arrays sized
    like an R-MAT 2^scale x 30 instance.  The BFS while-loops are counted
    once by cost_analysis (trip counts are data-dependent — documented),
    but the epoch's AGGREGATION — the object the paper studies — sits
    outside all loops, so its collective bytes are exact.

    ``metric`` is a single estimator name or a comma list
    (``"closeness,harmonic"``): the epoch step is lowered with that
    estimator stack and the record carries ``while_loops`` — the HLO
    while-instruction census proving a multi-metric step runs ONE BFS
    stream per sampling round (same while count as any single metric on
    the same stream; only the fold arithmetic widens).

    ``partitioned=True`` lowers the vertex-sharded cooperative epoch
    instead (repro.core.partition; DESIGN.md §Partitioning): the graph's
    frontier structure is split over the mesh and each BFS level runs
    the bitmap-scheduled frontier exchange (DESIGN.md §Frontier
    exchange).  Because the exchange sits INSIDE the level while-loop
    (counted once), the recorded all-gather bytes of the loop body ARE
    per-level exchange volume.  The HLO text contains BOTH protocol
    branches of the per-level ``lax.cond`` (sparse + dense fallback);
    the raw module total in ``full.collectives`` keeps that
    text-appears-once convention, and the record's ``exchange`` block
    carries the per-branch split from
    :func:`exchange_branch_accounting` — module all-gather bytes with
    the NOT-taken arm subtracted, under each protocol hypothesis — so
    no consumer needs to sum both arms.  It also carries the analytic
    per-protocol figures from
    :func:`repro.core.partition.exchange_plan` (dense, sparse-budget,
    and the static block budget itself), together with the per-device
    shard bytes vs the replicated-layout equivalent (the
    O(E) -> O(E / n_dev) claim, measured)."""
    import jax.numpy as jnp
    from repro.core.engine import _pad_len, draw_fold, make_epoch_step_spmd
    from repro.core.estimators import get_estimator
    from repro.core.estimators.base import RunContext
    from repro.core.kadabra import KadabraParams
    from repro.core.graph import Graph
    from repro.models.common import active_mesh

    mesh = make_production_mesh(multi_pod=mesh_name == "multi")
    n_dev = int(np.prod(mesh.devices.shape))
    v = 1 << rmat_scale
    e_dir = 2 * 30 * v          # 30|V| undirected edges, both directions
    e_pad = (e_dir // 128 + 2) * 128
    v_pad = _pad_len(v, n_dev)

    metrics = tuple(m.strip() for m in metric.split(",") if m.strip())
    ests = tuple(get_estimator(m) for m in metrics)
    if stream is None:
        stream = ("forward" if any(e.needs_forward for e in ests)
                  else "bidir")
    n_chan = sum(e.n_channels for e in ests)
    # representative R-MAT vertex diameter — static input of the epoch
    # step (closeness' distance cap); any small int lowers the same HLO
    ctx = RunContext(v, 12)

    sds = jax.ShapeDtypeStruct
    # every shipped estimator parameterizes the shared Bernstein rule
    # with a KadabraParams pytree, so the abstract params tuple is
    # uniform (only omega's provenance differs — VD bound vs Hoeffding)
    params = tuple(KadabraParams(
        eps=0.001, delta=0.1, omega=sds((), jnp.float32),
        log_inv_delta_l=sds((v,), jnp.float32),
        log_inv_delta_u=sds((v,), jnp.float32)) for _ in ests)

    # lower the batched sampling lane at an explicit width.  The graph
    # here is abstract (ShapeDtypeStructs — no diameter estimate to
    # resolve run_kadabra's per-instance B from), so batch_size=None
    # falls back to DEFAULT_SAMPLE_BATCH_SIZE; pass the width
    # resolve_sample_batch_size would pick (64 for R-MAT-like diameters)
    # to lower exactly run_kadabra's lane.  sample_batch clamps B to n0
    # (no point computing masked surplus columns), so the effective
    # width — what the compiled program actually runs — is min(B, n0);
    # record that, not the requested B.
    if batch_size is None:
        from repro.core.adaptive import DEFAULT_SAMPLE_BATCH_SIZE
        batch_size = DEFAULT_SAMPLE_BATCH_SIZE
    batch_size = max(1, min(batch_size, n0))

    exchange = None
    if partitioned:
        from repro.core.engine import make_epoch_step_sharded
        from repro.core.partition import (abstract_partitioned_graph,
                                          exchange_plan)
        from repro.kernels.frontier.ops import choose_csc_blocks
        block_v, block_e = choose_csc_blocks(v, batch_size)
        pg = abstract_partitioned_graph(v, e_dir, n_dev, block_v=block_v,
                                        block_e=block_e)
        shard_bytes = 4 * (2 * pg.shards.e_slots_per_shard
                           + 2 * pg.shards.n_edge_blocks)
        plan = exchange_plan(pg, batch_size)
        exchange = {
            "per_device_shard_bytes": int(shard_bytes),
            "replicated_csc_bytes_estimate": int(4 * (2 * e_dir
                                                      + 2 * e_dir // block_e)),
            "frontier_slice_bytes_per_level_dense":
                int(pg.v_pad * batch_size * 4),
            # the bitmap-scheduled protocol (DESIGN.md §Frontier
            # exchange): analytic per-level volumes of the two branches
            # the compiled cond carries, from the shared ExchangePlan
            "exchange_budget_blocks": int(plan.budget),
            "chunks_per_shard": int(plan.chunks_per_shard),
            "level_bytes_dense_protocol": int(plan.dense_bytes),
            "level_bytes_sparse_protocol": int(plan.sparse_bytes),
            "bitmap_bytes_per_level": int(plan.bitmap_bytes),
            "note": "loop-body all-gather bytes = one BFS level's "
                    "frontier exchange (while bodies counted once). "
                    "full.collectives is the raw module-text total and "
                    "holds BOTH cond branches; cond_branches below "
                    "reports each arm separately and the module total "
                    "with the not-taken arm removed — at runtime a "
                    "level moves level_bytes_sparse_protocol when its "
                    "occupancy fits exchange_budget_blocks on every "
                    "shard, level_bytes_dense_protocol otherwise",
        }
        step = make_epoch_step_sharded(draw_fold, mesh, ests, stream, ctx,
                                       n0, batch_size)
        args = (pg, params,
                sds((n_chan, v_pad), jnp.float32), sds((), jnp.int32),
                sds((n_chan, v_pad), jnp.float32), sds((), jnp.int32),
                sds((n_chan, v + 1), jnp.float32), sds((), jnp.int32),
                sds((2,), jnp.uint32))
    else:
        graph = Graph(
            indptr=sds((v + 1,), jnp.int32),
            indices=sds((e_pad,), jnp.int32),
            src=sds((e_pad,), jnp.int32), dst=sds((e_pad,), jnp.int32),
            degree=sds((v,), jnp.int32), n_nodes=v, n_edges=e_dir,
            max_degree=100_000)
        step = make_epoch_step_spmd(draw_fold, mesh, aggregation, ests,
                                    stream, ctx, n0, batch_size)
        args = (graph, params,
                sds((n_chan, v_pad), jnp.float32), sds((), jnp.int32),
                sds((n_dev, n_chan, v_pad), jnp.float32),
                sds((), jnp.int32),
                sds((n_dev, n_chan, v + 1), jnp.float32),
                sds((), jnp.int32),
                sds((n_dev, 2), jnp.uint32))
    with active_mesh(mesh):
        t0 = time.time()
        lowered = jax.jit(step).lower(*args)
        compiled = lowered.compile()
        t_compile = time.time() - t0
    ca = _cost_analysis(compiled)
    ma = compiled.memory_analysis()
    cell = ("epoch_part_rmat" if partitioned else "epoch_rmat")
    if metrics != ("betweenness",):
        cell += "_" + "_".join(metrics)
    if stream == "forward" and not any(e.needs_forward for e in ests):
        cell += "_fwd"          # explicit stream override in the name
    record = {
        "arch": "betweenness", "cell": f"{cell}{rmat_scale}",
        "mesh": mesh_name, "chips": n_dev, "family": "graph-sampling",
        "basis": "exact",
        "variant": "partitioned" if partitioned else aggregation,
        "sample_batch_size": batch_size,
        "metrics": list(metrics), "stream": stream, "channels": n_chan,
        "timestamp": time.strftime("%Y-%m-%d %H:%M:%S"),
        "full": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0)),
            "t_compile_s": t_compile,
            "collectives": collective_stats(compiled.as_text()),
            "while_loops": while_loop_stats(compiled.as_text()),
            "memory": {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "code_bytes": 0,
            },
        },
        "note": "BFS while-loop bodies counted once (data-dependent trip "
                "counts); aggregation collectives exact",
    }
    if exchange is not None:
        # split the per-level protocol cond by branch (taken-arm-only
        # totals); parsed from the same optimized HLO as
        # full.collectives, so the two subtract consistently
        exchange["cond_branches"] = exchange_branch_accounting(
            compiled.as_text())
        record["exchange"] = exchange
    record["extrapolated"] = _lin(record["full"])
    _write(record, out_dir)
    return record


def iter_assigned_cells():
    for arch_id in registry.all_ids():
        arch = registry.get(arch_id)
        if arch.family == "graph-sampling":
            continue
        for cell_name in arch.cells:
            yield arch_id, cell_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--betweenness", action="store_true",
                    help="lower the paper's own epoch step instead")
    ap.add_argument("--partitioned", action="store_true",
                    help="with --betweenness: lower the vertex-sharded "
                         "cooperative epoch (per-level frontier exchange)")
    ap.add_argument("--aggregation", default="hierarchical",
                    choices=["hierarchical", "flat", "root"])
    ap.add_argument("--metric", default="betweenness",
                    help="with --betweenness: estimator name or comma "
                         "list (e.g. closeness,harmonic) — multi-metric "
                         "steps prove the one-BFS-stream amortization "
                         "via the recorded while_loops census")
    ap.add_argument("--stream", default=None,
                    choices=["bidir", "forward"],
                    help="with --betweenness: override the draw stream "
                         "(default: forward iff a metric needs it)")
    ap.add_argument("--variant", default=None,
                    help="perf variant (fsdp, microN, fsdp_micro8, "
                         "noremat, chunk2048)")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.betweenness:
        for mesh_name in meshes:
            rec = run_betweenness(mesh_name, args.aggregation,
                                  out_dir=args.out,
                                  partitioned=args.partitioned,
                                  metric=args.metric,
                                  stream=args.stream)
            lane = "partitioned" if args.partitioned else args.aggregation
            print(f"[dryrun] {args.metric} x {mesh_name} x "
                  f"{lane}: ok", flush=True)
        return
    if args.all:
        cells = list(iter_assigned_cells())
    else:
        cells = [(args.arch, args.shape)]

    failures = []
    for arch_id, cell_name in cells:
        for mesh_name in meshes:
            fname = os.path.join(args.out, "{}__{}__{}.json".format(
                arch_id, cell_name, mesh_name))
            if args.skip_existing and os.path.exists(fname):
                print(f"[dryrun] skip existing {fname}", flush=True)
                continue
            t0 = time.time()
            try:
                rec = run_cell(arch_id, cell_name, mesh_name, args.out,
                               variant=args.variant)
                status = ("SKIP(" + rec["skipped"][:40] + "...)"
                          if "skipped" in rec else "ok")
                print(f"[dryrun] {arch_id} x {cell_name} x {mesh_name}: "
                      f"{status} in {time.time()-t0:.1f}s", flush=True)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures.append((arch_id, cell_name, mesh_name, str(e)))
                traceback.print_exc()
                print(f"[dryrun] FAIL {arch_id} x {cell_name} x "
                      f"{mesh_name}: {e}", flush=True)
    if failures:
        print(f"[dryrun] {len(failures)} failures:")
        for f in failures:
            print("   ", f[:3])
        raise SystemExit(1)
    print("[dryrun] all requested cells compiled.")


if __name__ == "__main__":
    main()
