"""Embedding substrate for the recsys stack.

JAX has no native ``nn.EmbeddingBag`` and no CSR sparse — per the
assignment, both are built here as part of the system:

  * ``embedding_bag`` — ragged multi-hot bags via ``jnp.take`` +
    ``jax.ops.segment_sum`` (sum/mean), sentinel-padded.
  * ``sharded_lookup`` — row-sharded tables (P("model", None)) with a
    mask-and-psum lookup inside shard_map: each TP shard gathers the ids it
    owns locally and a single psum reassembles the embedding — the lookup
    (the recsys hot path) never materializes the full table anywhere.
    Gradients flow through as local scatter-adds (autodiff of the gather),
    so optimizer state stays row-sharded too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["embedding_bag", "sharded_lookup"]


def embedding_bag(table: jax.Array, ids: jax.Array, bag_ids: jax.Array,
                  n_bags: int, *, mode: str = "mean") -> jax.Array:
    """EmbeddingBag: ids i32[n_idx] (sentinel = vocab → zero row),
    bag_ids i32[n_idx] sorted. → f[n_bags, d]."""
    v, d = table.shape
    tbl = jnp.concatenate([table, jnp.zeros((1, d), table.dtype)], 0)
    vals = jnp.take(tbl, jnp.minimum(ids, v), axis=0)
    valid = (ids < v).astype(table.dtype)
    vals = vals * valid[:, None]
    out = jax.ops.segment_sum(vals, bag_ids, num_segments=n_bags,
                              indices_are_sorted=True)
    if mode == "mean":
        cnt = jax.ops.segment_sum(valid, bag_ids, num_segments=n_bags,
                                  indices_are_sorted=True)
        out = out / jnp.maximum(cnt[:, None], 1.0)
    return out


def sharded_lookup(table: jax.Array, ids: jax.Array, mesh,
                   *, batch_axes: tuple[str, ...] = ()) -> jax.Array:
    """Row-sharded embedding lookup: table P("model", None), ids replicated
    or sharded over ``batch_axes``. Returns embeddings sharded like ids."""
    if "model" not in mesh.axis_names:
        return jnp.take(table, ids, axis=0)
    tp = mesh.shape["model"]
    v, d = table.shape
    rows = v // tp

    def local(tbl, ids_loc):
        r = jax.lax.axis_index("model")
        lo = r * rows
        rel = ids_loc - lo
        ok = (rel >= 0) & (rel < rows)
        emb = jnp.take(tbl, jnp.clip(rel, 0, rows - 1), axis=0)
        emb = emb * ok[..., None].astype(emb.dtype)
        return jax.lax.psum(emb, "model")

    ba = tuple(a for a in batch_axes if a in mesh.axis_names)
    id_spec = P(ba, *([None] * (ids.ndim - 1))) if ba else P(
        *([None] * ids.ndim))
    out_spec = P(ba, *([None] * ids.ndim)) if ba else P(
        *([None] * (ids.ndim + 1)))
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("model", None), id_spec),
        out_specs=out_spec, check_vma=False)(table, ids)
