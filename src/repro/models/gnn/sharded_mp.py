"""2-D sharded message passing — the GNN collective hillclimb (§Perf).

The baseline GNN cells let GSPMD partition ``segment_sum`` over edge/node
arrays, which materializes gather operands with all-gathers (the
graphsage-reddit/ogb_products cell is the most collective-bound in the
baseline table). This module reuses the ψ-score 2-D block-cyclic partition
(DESIGN.md §4) for *feature matrices*: device (r, c) owns the edges with
src ∈ block-cyclic row r, dst ∈ contiguous column block c, and one layer of
mean-aggregation costs exactly

    psum_scatter [Nc, F]  over the src rows   (reduce of local partials)
  + all_gather   [N/d, F] over the columns    (reassemble the row shard)

per layer — the same bandwidth-optimal schedule as the ψ push, versus the
baseline's full-activation all-gathers. ``GraphSAGE`` is the instantiated
consumer (sharded_sage_apply); the pattern generalizes to any src-feature
message function.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...graphs.partition import Partition2D, partition_2d

__all__ = ["ShardedGraph", "build_sharded_graph", "make_sage_layer",
           "sharded_sage_apply"]


@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """Edge blocks + degree tables in the 2-D layouts (a pytree)."""
    src_local: jax.Array    # i32[d, mo, e_max] block-cyclic src ids
    dst_local: jax.Array    # i32[d, mo, e_max] contiguous dst ids
    deg_piece: jax.Array    # f[d, mo, q] in-degree in piece layout


jax.tree_util.register_dataclass(
    ShardedGraph, data_fields=["src_local", "dst_local", "deg_piece"],
    meta_fields=[])


def build_sharded_graph(graph, mesh: Mesh, *, bidirectional: bool = True
                        ) -> tuple[Partition2D, ShardedGraph]:
    axes = mesh.axis_names
    d = int(np.prod([mesh.shape[a] for a in axes[:-1]]))
    mo = mesh.shape[axes[-1]]
    g = graph
    if bidirectional:
        from ...graphs.structure import Graph
        g = Graph(g.n, np.concatenate([g.src, g.dst]),
                  np.concatenate([g.dst, g.src]), name=g.name)
    part = partition_2d(g, d, mo)
    deg = np.zeros(part.n_pad, np.float32)
    np.add.at(deg[: g.n], g.dst, 1.0)
    src_axes = axes[:-1]
    grid = NamedSharding(mesh, P(src_axes, axes[-1], None))
    sg = ShardedGraph(
        src_local=jax.device_put(part.src_local, grid),
        dst_local=jax.device_put(part.dst_local, grid),
        deg_piece=jax.device_put(part.to_piece_layout(deg), grid))
    return part, sg


def make_sage_layer(part: Partition2D, mesh: Mesh):
    """One mean-aggregate + dense update layer on 2-D sharded features.

    x: f[d, local_n, F] (block-cyclic src layout, sharded over src axes,
    replicated over the column axis). weights replicated. Returns same
    layout. Collectives: one psum_scatter + one all_gather of features.
    """
    axes = mesh.axis_names
    src_axes = axes[:-1]
    col_axis = axes[-1]
    nc = part.nc
    q = part.q

    def local(x, sg: ShardedGraph, w_self, b_self, w_neigh, b_neigh):
        x_loc = x[0]                               # [local_n, F]
        f = x_loc.shape[-1]
        src_ids = sg.src_local[0, 0]
        dst_ids = sg.dst_local[0, 0]
        x_pad = jnp.concatenate([x_loc, jnp.zeros((1, f), x.dtype)], 0)
        msgs = x_pad[src_ids]                      # [e_max, F]
        partial = jax.ops.segment_sum(
            msgs, dst_ids, nc + 1, indices_are_sorted=True)[:nc]
        agg_piece = jax.lax.psum_scatter(
            partial, src_axes, scatter_dimension=0, tiled=True)  # [q, F]
        mean_piece = agg_piece / jnp.maximum(sg.deg_piece[0, 0][:, None], 1)
        # self features of this piece = local slice c·q … (c+1)·q of row r
        c_idx = jax.lax.axis_index(col_axis)
        self_piece = jax.lax.dynamic_slice_in_dim(x_loc, c_idx * q, q, 0)
        h = jax.nn.relu(self_piece @ w_self + b_self +
                        mean_piece @ w_neigh + b_neigh)
        h = h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True),
                            1e-6)
        # reassemble this row's block-cyclic shard for the next layer
        return jax.lax.all_gather(h, col_axis, axis=0, tiled=True)[None]

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(src_axes, None, None),
                  ShardedGraph(src_local=P(src_axes, col_axis, None),
                               dst_local=P(src_axes, col_axis, None),
                               deg_piece=P(src_axes, col_axis, None)),
                  P(None, None), P(None), P(None, None), P(None)),
        out_specs=P(src_axes, None, None), check_vma=False)


def sharded_sage_apply(params, x_src_layout, part: Partition2D, sg,
                       mesh: Mesh, cfg):
    """Full sharded GraphSAGE forward: features stay 2-D sharded end-to-end.

    x_src_layout: f[d, local_n, d_feat] (see Partition2D.to_src_layout).
    Returns logits in the same layout.
    """
    h = x_src_layout
    for lyr in params["layers"]:
        layer_fn = make_sage_layer(part, mesh)
        h = layer_fn(h, sg, lyr["w_self"]["w"], lyr["w_self"]["b"],
                     lyr["w_neigh"]["w"], lyr["w_neigh"]["b"])
    return jnp.einsum("dnf,fc->dnc", h, params["head"]["w"]) + \
        params["head"]["b"]
