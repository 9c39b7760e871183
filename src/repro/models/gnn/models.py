"""The four assigned GNN architectures.

  graphsage  — 2 layers, mean aggregator (Hamilton et al. '17)
  egnn       — 4 layers, E(n)-equivariant (Satorras et al. '21)
  nequip     — 5 layers, l_max=2 tensor-product messages (Batzner '21)
  mace       — 2 layers, correlation-order-3 ACE messages (Batatia '22)

All share the GraphBatch substrate; equivariant models use the numerical
coupling tensors of ``irreps.py`` (exact, intertwiner-verified).  MACE's
symmetric contraction is realized as iterated CG products
(B2 = (A (x) A), B3 = (B2 (x) A)) — spanning the correlation-3 space;
DESIGN.md §Arch-applicability records this simplification.

Each model: init_params(key, cfg) / forward(params, batch, cfg) /
loss(params, batch, cfg) / param_specs(cfg).  Node/edge tensors shard over
("pod","data") (see configs); parameters are small and replicated.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..common import dense_init, gelu, ones_init, rms_norm
from . import irreps
from .message_passing import (GraphBatch, gather_src, graph_regression_loss,
                              node_classification_loss, scatter_dst,
                              scatter_mean)

EDGE_SPEC = P(("pod", "data"))
NODE_SPEC = P(("pod", "data"), None)


# ===========================================================================
# GraphSAGE
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class SageConfig:
    n_layers: int = 2
    d_in: int = 602
    d_hidden: int = 128
    n_classes: int = 41
    n_types: int = 32          # fallback embedding when x is absent
    aggregator: str = "mean"
    # "sharded" (over the data tier) | "replicated" (gathers vanish;
    # node tables up to ~1 GB fit every HBM) — §Perf lever
    node_sharding: str = "sharded"


def sage_init(key, cfg: SageConfig):
    keys = jax.random.split(key, 2 * cfg.n_layers + 2)
    d_prev = cfg.d_hidden
    params = {
        "embed_in": dense_init(keys[0], (cfg.d_in, cfg.d_hidden),
                               jnp.float32),
        "embed_z": dense_init(keys[1], (cfg.n_types, cfg.d_hidden),
                              jnp.float32),
        "layers": [],
        "head": None,
    }
    for i in range(cfg.n_layers):
        params["layers"].append({
            "w_self": dense_init(keys[2 + 2 * i],
                                 (d_prev, cfg.d_hidden), jnp.float32),
            "w_neigh": dense_init(keys[3 + 2 * i],
                                  (d_prev, cfg.d_hidden), jnp.float32),
        })
    params["head"] = dense_init(keys[-1], (cfg.d_hidden, cfg.n_classes),
                                jnp.float32)
    return params


def sage_forward(params, batch: GraphBatch, cfg: SageConfig):
    h = batch.x.astype(jnp.float32) @ params["embed_in"] \
        + params["embed_z"][batch.z]
    for lp in params["layers"]:
        neigh = scatter_mean(gather_src(h, batch.src), batch.dst,
                             h.shape[0], batch.edge_mask)
        h = jax.nn.relu(h @ lp["w_self"] + neigh @ lp["w_neigh"])
        h = h / jnp.maximum(
            jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)
    return h @ params["head"]


def sage_loss(params, batch: GraphBatch, cfg: SageConfig):
    return node_classification_loss(sage_forward(params, batch, cfg), batch)


# ===========================================================================
# EGNN
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class EgnnConfig:
    n_layers: int = 4
    d_hidden: int = 64
    n_types: int = 32
    d_in: int = 0              # optional extra features
    n_classes: int = 0         # 0 => graph regression head
    update_pos: bool = True
    # "sharded" (over the data tier) | "replicated" (gathers vanish;
    # node tables up to ~1 GB fit every HBM) — §Perf lever
    node_sharding: str = "sharded"
    # dtype of gathered/aggregated messages: "f32" | "bf16" (halves the
    # cross-shard gather + psum payloads) — §Perf lever
    agg_dtype: str = "f32"
    # explicit-collective message passing: the whole forward runs inside
    # shard_map with hand-placed all_gather / psum_scatter (GSPMD's
    # scatter handling pins an all-reduce otherwise) — §Perf lever
    partitioned: bool = False


def _mlp_init(key, dims):
    keys = jax.random.split(key, len(dims) - 1)
    return [dense_init(k, (a, b), jnp.float32)
            for k, a, b in zip(keys, dims[:-1], dims[1:])]


def _mlp(ws, x):
    for i, w in enumerate(ws):
        x = x @ w
        if i < len(ws) - 1:
            x = jax.nn.silu(x)
    return x


def egnn_init(key, cfg: EgnnConfig):
    keys = jax.random.split(key, cfg.n_layers * 3 + 3)
    d = cfg.d_hidden
    params = {
        "embed_z": dense_init(keys[0], (cfg.n_types, d), jnp.float32),
        "embed_x": dense_init(keys[1], (max(cfg.d_in, 1), d), jnp.float32),
        "layers": [],
        "head": dense_init(keys[2], (d, max(cfg.n_classes, 1)),
                           jnp.float32),
    }
    for i in range(cfg.n_layers):
        params["layers"].append({
            "edge_mlp": _mlp_init(keys[3 + 3 * i], (2 * d + 1, d, d)),
            "coord_mlp": _mlp_init(keys[4 + 3 * i], (d, d, 1)),
            "node_mlp": _mlp_init(keys[5 + 3 * i], (2 * d, d, d)),
        })
    return params


def egnn_forward(params, batch: GraphBatch, cfg: EgnnConfig):
    n = batch.x.shape[0]
    h = params["embed_z"][batch.z]
    if cfg.d_in:
        h = h + batch.x.astype(jnp.float32) @ params["embed_x"]
    pos = batch.pos.astype(jnp.float32)
    # bf16 mode: hidden states, edge messages and therefore every
    # cross-shard gather / psum payload run in bf16 end-to-end (the MLP
    # matmuls accumulate in f32 on the MXU); f32 mode is exact
    mdt = jnp.bfloat16 if cfg.agg_dtype == "bf16" else jnp.float32
    h = h.astype(mdt)
    for lp in params["layers"]:
        rel = pos[batch.src] - pos[batch.dst]
        d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)
        m_in = jnp.concatenate([h[batch.src], h[batch.dst],
                                d2.astype(mdt)], axis=-1)
        m = _mlp([w.astype(mdt) for w in lp["edge_mlp"]], m_in) \
            * batch.edge_mask[:, None].astype(mdt)
        agg = scatter_dst(m, batch.dst, n)
        h = h + _mlp([w.astype(mdt) for w in lp["node_mlp"]],
                     jnp.concatenate([h, agg], axis=-1))
        if cfg.update_pos:
            # E(n)-equivariant coordinate update: x_i += mean_j (x_i - x_j) phi(m_ij)
            coef = (_mlp([w.astype(mdt) for w in lp["coord_mlp"]], m)
                    * batch.edge_mask[:, None].astype(mdt)) \
                .astype(jnp.float32)
            # note rel = x_src - x_dst; update receiver (dst)
            delta = scatter_mean(-rel * coef, batch.dst, n, batch.edge_mask)
            pos = pos + delta
    return h, pos


def egnn_forward_partitioned(params, batch: GraphBatch, cfg: EgnnConfig,
                             mesh):
    """EGNN forward inside shard_map: node arrays row-sharded over ALL
    mesh axes, edge arrays sharded over all axes; per layer exactly one
    all_gather (node states out) and two psum_scatters (messages +
    coordinate updates back).  See message_passing.sharded_aggregate."""
    import functools
    from jax.sharding import PartitionSpec as P
    from .message_passing import sharded_aggregate, sharded_layer_collectives
    alla = tuple(mesh.axis_names)
    n = batch.x.shape[0]
    nspec = P(alla, None)
    espec = P(alla)
    prep = jax.tree.map(lambda _: P(), params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(prep, nspec, P(alla), nspec, espec, espec, espec),
        out_specs=(nspec, nspec), check_vma=False)
    def fwd(params, x_loc, z_loc, pos_loc, src, dst, emask):
        h_loc = params["embed_z"][z_loc]
        if cfg.d_in:
            h_loc = h_loc + x_loc.astype(jnp.float32) @ params["embed_x"]
        pos_loc = pos_loc.astype(jnp.float32)
        for lp in params["layers"]:
            h = sharded_layer_collectives(h_loc, alla)      # (N, D)
            pos = sharded_layer_collectives(pos_loc, alla)  # (N, 3)
            rel = pos[src] - pos[dst]
            d2 = jnp.sum(rel * rel, axis=-1, keepdims=True)
            m_in = jnp.concatenate([h[src], h[dst], d2], axis=-1)
            m = _mlp(lp["edge_mlp"], m_in) * emask[:, None]
            agg_loc = sharded_aggregate(m, dst, n, alla)
            h_loc = h_loc + _mlp(lp["node_mlp"],
                                 jnp.concatenate([h_loc, agg_loc], -1))
            if cfg.update_pos:
                coef = _mlp(lp["coord_mlp"], m) * emask[:, None]
                num = sharded_aggregate(
                    jnp.concatenate([-rel * coef, emask[:, None]], -1),
                    dst, n, alla)
                pos_loc = pos_loc + num[:, :3] / jnp.maximum(
                    num[:, 3:], 1.0)
        return h_loc, pos_loc

    return fwd(params, batch.x, batch.z, batch.pos, batch.src, batch.dst,
               batch.edge_mask)


def egnn_loss(params, batch: GraphBatch, cfg: EgnnConfig, mesh=None):
    if cfg.partitioned and mesh is not None:
        h, _pos = egnn_forward_partitioned(params, batch, cfg, mesh)
    else:
        h, _pos = egnn_forward(params, batch, cfg)
    out = h.astype(jnp.float32) @ params["head"]
    if cfg.n_classes:
        return node_classification_loss(out, batch)
    return graph_regression_loss(out[:, 0], batch)


# ===========================================================================
# NequIP
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class NequipConfig:
    n_layers: int = 5
    d_hidden: int = 32          # channels per l
    l_max: int = 2
    n_rbf: int = 8
    cutoff: float = 5.0
    n_types: int = 32
    n_classes: int = 0
    # "sharded" (over the data tier) | "replicated" (gathers vanish;
    # node tables up to ~1 GB fit every HBM) — §Perf lever
    node_sharding: str = "sharded"


def _radial_basis(r, n_rbf: int, cutoff: float):
    """Bessel-style radial basis with a smooth polynomial cutoff."""
    r = jnp.maximum(r, 1e-6)
    n = jnp.arange(1, n_rbf + 1, dtype=jnp.float32)
    basis = jnp.sin(np.pi * n * r[:, None] / cutoff) / r[:, None]
    x = jnp.clip(r / cutoff, 0.0, 1.0)
    env = 1.0 - 10.0 * x ** 3 + 15.0 * x ** 4 - 6.0 * x ** 5
    return basis * env[:, None]


def nequip_init(key, cfg: NequipConfig):
    c = cfg.d_hidden
    pth = irreps.paths(cfg.l_max)
    keys = jax.random.split(key, cfg.n_layers + 2)
    params = {"embed_z": dense_init(keys[0], (cfg.n_types, c), jnp.float32),
              "layers": [],
              "head": _mlp_init(keys[1], (c, c, max(cfg.n_classes, 1)))}
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[2 + i], 2 + len(pth))
        layer = {
            # radial MLP per path: n_rbf -> channels
            "radial": {pq: _mlp_init(lk[2 + j], (cfg.n_rbf, c, c))
                       for j, pq in enumerate(pth)},
            # post-aggregation per-l channel mixers
            "mix": {l: dense_init(lk[0], (c, c), jnp.float32,
                                  scale=1.0 / np.sqrt(cfg.n_layers))
                    for l in range(cfg.l_max + 1)},
            "gate": dense_init(lk[1], (c, (cfg.l_max + 1) * c), jnp.float32),
        }
        params["layers"].append(layer)
    return params


def nequip_forward(params, batch: GraphBatch, cfg: NequipConfig):
    n = batch.x.shape[0]
    rel = (batch.pos[batch.src] - batch.pos[batch.dst]).astype(jnp.float32)
    r = jnp.linalg.norm(rel, axis=-1)
    unit = rel / jnp.maximum(r, 1e-6)[:, None]
    # degenerate (zero-length / self-loop) edges have no direction: mask
    # them out entirely so Y_l(0) cannot leak a non-equivariant constant
    live = batch.edge_mask * (r > 1e-6)
    rbf = _radial_basis(r, cfg.n_rbf, cfg.cutoff) * live[:, None]
    ysh = irreps.sh_all(unit, cfg.l_max)

    feats = {0: params["embed_z"][batch.z][:, :, None]}
    for lp in params["layers"]:
        # --- tensor-product messages per edge ---------------------------
        edge_feats = {l: f[batch.src] for l, f in feats.items()}
        weights = {pq: _mlp(lp["radial"][pq], rbf)
                   for pq in lp["radial"]}
        msgs = irreps.tensor_product(edge_feats, ysh, weights, cfg.l_max)
        # --- aggregate + mix + gate --------------------------------------
        new = {}
        for l, m in msgs.items():
            agg = scatter_dst(
                m.reshape(m.shape[0], -1) * batch.edge_mask[:, None],
                batch.dst, n).reshape(n, -1, irreps.DIMS[l])
            new[l] = jnp.einsum("ncx,cd->ndx", agg, lp["mix"][l])
        gates = jax.nn.sigmoid(feats[0][:, :, 0] @ lp["gate"]).reshape(
            n, cfg.l_max + 1, -1)
        out = {}
        for l in range(cfg.l_max + 1):
            upd = new.get(l)
            if upd is None:
                continue
            if l == 0:
                upd = jax.nn.silu(upd)
            upd = upd * gates[:, l, :, None]
            prev = feats.get(l)
            out[l] = upd if prev is None else prev + upd
        feats = out
    energy = _mlp(params["head"], feats[0][:, :, 0])
    return feats, energy


def nequip_loss(params, batch: GraphBatch, cfg: NequipConfig):
    feats, out = nequip_forward(params, batch, cfg)
    if cfg.n_classes:
        return node_classification_loss(out, batch)
    return graph_regression_loss(out[:, 0], batch)


# ===========================================================================
# MACE
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class MaceConfig:
    n_layers: int = 2
    d_hidden: int = 128
    l_max: int = 2
    correlation: int = 3
    n_rbf: int = 8
    cutoff: float = 5.0
    n_types: int = 32
    n_classes: int = 0
    # "sharded" (over the data tier) | "replicated" (gathers vanish;
    # node tables up to ~1 GB fit every HBM) — §Perf lever
    node_sharding: str = "sharded"


def mace_init(key, cfg: MaceConfig):
    c = cfg.d_hidden
    pth = irreps.paths(cfg.l_max)
    keys = jax.random.split(key, cfg.n_layers + 2)
    params = {"embed_z": dense_init(keys[0], (cfg.n_types, c), jnp.float32),
              "layers": [],
              "head": _mlp_init(keys[1], (c, c, max(cfg.n_classes, 1)))}
    for i in range(cfg.n_layers):
        lk = jax.random.split(keys[2 + i], 4 + len(pth))
        params["layers"].append({
            "radial": {pq: _mlp_init(lk[4 + j], (cfg.n_rbf, c, c))
                       for j, pq in enumerate(pth)},
            # per-correlation-order, per-l mixing weights
            "mix_a": {l: dense_init(lk[0], (c, c), jnp.float32)
                      for l in range(cfg.l_max + 1)},
            "mix_b2": {l: dense_init(lk[1], (c, c), jnp.float32,
                                     scale=0.5)
                       for l in range(cfg.l_max + 1)},
            "mix_b3": {l: dense_init(lk[2], (c, c), jnp.float32,
                                     scale=0.25)
                       for l in range(cfg.l_max + 1)},
            "update": dense_init(lk[3], (c, c), jnp.float32),
        })
    return params


def mace_forward(params, batch: GraphBatch, cfg: MaceConfig):
    n = batch.x.shape[0]
    rel = (batch.pos[batch.src] - batch.pos[batch.dst]).astype(jnp.float32)
    r = jnp.linalg.norm(rel, axis=-1)
    unit = rel / jnp.maximum(r, 1e-6)[:, None]
    live = batch.edge_mask * (r > 1e-6)   # mask degenerate edges (see nequip)
    rbf = _radial_basis(r, cfg.n_rbf, cfg.cutoff) * live[:, None]
    ysh = irreps.sh_all(unit, cfg.l_max)

    feats = {0: params["embed_z"][batch.z][:, :, None]}
    for lp in params["layers"]:
        # --- atomic basis A_i: aggregated TP of neighbors with Y ---------
        edge_feats = {l: f[batch.src] for l, f in feats.items()}
        weights = {pq: _mlp(lp["radial"][pq], rbf) for pq in lp["radial"]}
        msgs = irreps.tensor_product(edge_feats, ysh, weights, cfg.l_max)
        A = {}
        for l, m in msgs.items():
            A[l] = scatter_dst(
                m.reshape(m.shape[0], -1) * batch.edge_mask[:, None],
                batch.dst, n).reshape(n, -1, irreps.DIMS[l])
        # --- higher-order products (ACE, correlation 3 via iterated CG) --
        B2 = irreps.tensor_product(A, {l: a for l, a in A.items()}, {},
                                   cfg.l_max)
        B3 = irreps.tensor_product(B2, {l: a for l, a in A.items()}, {},
                                   cfg.l_max)
        new = {}
        for l in range(cfg.l_max + 1):
            acc = None
            for tree, mix in ((A, "mix_a"), (B2, "mix_b2"), (B3, "mix_b3")):
                if l in tree:
                    term = jnp.einsum("ncx,cd->ndx", tree[l], lp[mix][l])
                    acc = term if acc is None else acc + term
            if acc is None:
                continue
            if l == 0:
                acc = jax.nn.silu(acc)
                acc = jnp.einsum("ncx,cd->ndx", acc, lp["update"])
            prev = feats.get(l)
            new[l] = acc if prev is None else prev + acc
        feats = new
    energy = _mlp(params["head"], feats[0][:, :, 0])
    return feats, energy


def mace_loss(params, batch: GraphBatch, cfg: MaceConfig):
    feats, out = mace_forward(params, batch, cfg)
    if cfg.n_classes:
        return node_classification_loss(out, batch)
    return graph_regression_loss(out[:, 0], batch)
