"""Decoder-only transformer family (TinyLlama / Yi / Nemotron / Mixtral).

Pure functional JAX: params are pytrees stacked over layers and consumed by
``lax.scan`` (keeps HLO size O(1) in depth — essential for compiling 96-layer
configs on the 512-device dry-run), with ``jax.checkpoint`` around the layer
body for activation rematerialization.

Features per the assigned configs:
  * GQA attention (n_kv_heads < n_heads) with RoPE,
  * flash-style blocked attention (see ``attention.py``) — banded O(S·W)
    schedule for sliding-window configs (Mixtral long_500k),
  * SwiGLU or squared-ReLU (Nemotron) FFN,
  * top-2 MoE (Mixtral) with TP-sharded experts and local token dispatch
    inside a nested shard_map (DESIGN.md: no all-to-all at E=8 ≤ TP=16),
  * grad accumulation + remat for the ≥100B-param memory envelope.

Sharding is GSPMD-style: pjit + with_sharding_constraint. Axis vocabulary:
batch → ("pod","data") (present axes only), TP (heads / d_ff / vocab) →
"model", FSDP (the other matrix dim of each weight) → ("pod","data").
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .attention import attention

__all__ = ["MoECfg", "LMConfig", "init_params", "param_specs", "forward",
           "loss_fn", "make_train_step", "make_prefill", "make_decode_step",
           "init_cache", "cache_specs", "count_params", "active_params"]

TP = "model"


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _dp_size(mesh) -> int:
    import numpy as _np
    return int(_np.prod([mesh.shape[a] for a in dp_axes(mesh)])) if         dp_axes(mesh) else 1


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int | None = None
    act: str = "swiglu"                  # "swiglu" | "sq_relu"
    moe: MoECfg | None = None
    sliding_window: int | None = None
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16            # activation/compute dtype
    param_dtype: Any = jnp.bfloat16
    remat: bool = True
    accum_steps: int = 1
    optimizer: str = "adamw"             # "adafactor" for the ≥100B cells
    q_block: int = 512                   # flash attention block sizes
    k_block: int = 1024
    fsdp: bool = True                    # shard weights over the batch axes
    unroll_layers: bool = False          # probe mode: unroll the layer scan

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def count_params(cfg: LMConfig) -> int:
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    attn = d * cfg.q_dim + 2 * d * cfg.kv_dim + cfg.q_dim * d
    if cfg.moe:
        ffn = cfg.moe.n_experts * (3 if cfg.act == "swiglu" else 2) * d * f \
            + d * cfg.moe.n_experts
    else:
        ffn = (3 if cfg.act == "swiglu" else 2) * d * f
    return cfg.n_layers * (attn + ffn + 2 * d) + 2 * v * d + d


def active_params(cfg: LMConfig) -> int:
    """Params touched per token (MoE: top-k experts) — for MODEL_FLOPS 6ND."""
    d, f = cfg.d_model, cfg.d_ff
    attn = d * cfg.q_dim + 2 * d * cfg.kv_dim + cfg.q_dim * d
    n_ff = (3 if cfg.act == "swiglu" else 2) * d * f
    ffn = (cfg.moe.top_k * n_ff + d * cfg.moe.n_experts) if cfg.moe else n_ff
    return cfg.n_layers * (attn + ffn + 2 * d) + 2 * cfg.vocab * d + d


# --------------------------------------------------------------------- #
# Params
# --------------------------------------------------------------------- #
def init_params(cfg: LMConfig, key: jax.Array) -> dict:
    d, f, v, L = cfg.d_model, cfg.d_ff, cfg.vocab, cfg.n_layers
    k = iter(jax.random.split(key, 16))
    pd = cfg.param_dtype

    def dense(key, *shape, scale=None):
        scale = scale or 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(pd)

    layer = dict(
        wq=dense(next(k), L, d, cfg.q_dim),
        wk=dense(next(k), L, d, cfg.kv_dim),
        wv=dense(next(k), L, d, cfg.kv_dim),
        wo=dense(next(k), L, cfg.q_dim, d),
        norm1=jnp.ones((L, d), pd),
        norm2=jnp.ones((L, d), pd),
    )
    if cfg.moe:
        E = cfg.moe.n_experts
        layer["router"] = dense(next(k), L, d, E)
        layer["w1"] = dense(next(k), L, E, d, f)
        layer["w2"] = dense(next(k), L, E, f, d, scale=1 / math.sqrt(f))
        if cfg.act == "swiglu":
            layer["w3"] = dense(next(k), L, E, d, f)
    else:
        layer["w1"] = dense(next(k), L, d, f)
        layer["w2"] = dense(next(k), L, f, d, scale=1 / math.sqrt(f))
        if cfg.act == "swiglu":
            layer["w3"] = dense(next(k), L, d, f)
    return dict(
        embed=dense(next(k), v, d, scale=1.0),
        lm_head=dense(next(k), d, v),
        final_norm=jnp.ones((d,), pd),
        layers=layer,
    )


def param_specs(cfg: LMConfig, mesh) -> dict:
    """TP on heads/d_ff/vocab; FSDP (other matrix dim) on the batch axes.

    ``cfg.fsdp=False`` (models whose optimizer state fits per TP shard, e.g.
    TinyLlama) keeps weights replicated across the batch axes — saves the
    per-step weight all-gathers entirely (§Perf).
    """
    dp = dp_axes(mesh) if cfg.fsdp else None
    layer = dict(
        wq=P(None, dp, TP),
        wk=P(None, dp, TP),
        wv=P(None, dp, TP),
        wo=P(None, TP, dp),
        norm1=P(None, None),
        norm2=P(None, None),
    )
    if cfg.moe:
        layer["router"] = P(None, None, None)
        layer["w1"] = P(None, None, dp, TP)
        layer["w2"] = P(None, None, TP, dp)
        if cfg.act == "swiglu":
            layer["w3"] = P(None, None, dp, TP)
    else:
        layer["w1"] = P(None, dp, TP)
        layer["w2"] = P(None, TP, dp)
        if cfg.act == "swiglu":
            layer["w3"] = P(None, dp, TP)
    return dict(embed=P(TP, dp), lm_head=P(dp, TP), final_norm=P(None),
                layers=layer)


# --------------------------------------------------------------------- #
# Building blocks
# --------------------------------------------------------------------- #
def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            ).astype(x.dtype) * scale


def _rope(x, positions, theta):
    """x: [B, S, H, Dh]; positions: [B, S] absolute token positions."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = (1.0 / theta) ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _moe_ffn(x, lp, cfg: LMConfig, mesh):
    """Top-k MoE: local token dispatch, TP-sharded experts, one psum."""
    import numpy as np
    moe = cfg.moe
    E, K = moe.n_experts, moe.top_k
    b, s, d = x.shape
    swiglu = cfg.act == "swiglu"
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if b % max(1, dp_size) != 0:
        dp = ()          # tiny batches (long_500k B=1): replicate tokens

    def local(x_loc, router, w1, w2, w3):
        tl = x_loc.shape[0] * x_loc.shape[1]
        xf = x_loc.reshape(tl, d)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        gates, eidx = jax.lax.top_k(jax.nn.softmax(logits, -1), K)
        gates = gates / jnp.sum(gates, -1, keepdims=True)
        cap = max(8, int(K * tl / E * moe.capacity_factor))

        flat_e = eidx.reshape(-1)                         # [K·T]
        order = jnp.argsort(flat_e)                       # stable
        tok = order // K
        sorted_e = flat_e[order]
        counts = jnp.bincount(flat_e, length=E)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(K * tl) - starts[sorted_e]
        keep = pos < cap
        slot = jnp.where(keep, sorted_e * cap + pos, E * cap)

        buf = jnp.zeros((E * cap + 1, d), x_loc.dtype).at[slot].set(xf[tok])
        h = buf[:E * cap].reshape(E, cap, d)
        if swiglu:
            hh = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, w1)) * \
                jnp.einsum("ecd,edf->ecf", h, w3)
        else:
            hh = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", h, w1)))
        y = jnp.einsum("ecf,efd->ecd", hh, w2).reshape(E * cap, d)
        y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)], 0)
        gath = y[slot] * gates.reshape(-1)[order][:, None].astype(y.dtype)
        out = jnp.zeros((tl, d), x_loc.dtype).at[tok].add(gath)
        out = jax.lax.psum(out, TP)
        return out.reshape(x_loc.shape)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None), P(None, None, TP),
                  P(None, TP, None), P(None, None, TP)),
        out_specs=P(dp, None, None), check_vma=False,
    )(x, lp["router"], lp["w1"], lp["w2"],
      lp["w3"] if swiglu else lp["w1"])


def _dense_ffn(x, lp, cfg: LMConfig, cst, dp):
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ lp["w1"]) * (x @ lp["w3"])
    else:
        h = jnp.square(jax.nn.relu(x @ lp["w1"]))
    # §Perf iteration 1: constraining (None, None, TP) here replicated the
    # batch axis — XLA materialized [B_full, S, ff/TP] f32 activations and
    # all-gathered their gradients (≈2.4 GB/layer/device on tinyllama
    # train_4k). Keeping the batch axes sharded removes those collectives.
    h = cst(h, dp, None, TP)
    return h @ lp["w2"]


def _make_cst(mesh):
    def cst(x, *spec):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))
    return cst


def _embed_lookup(embed, tokens, cfg: LMConfig, mesh, dp):
    """Vocab-sharded embedding gather via shard_map (mask + psum).

    §Perf iteration 2 — REFUTED and therefore unused: the hypothesis was
    that XLA's gather backward all-gathers the activation gradient; after
    the iteration-1 fix that all-gather no longer exists (it was fallout of
    the bad FFN constraint), and this form *adds* a psum of x
    (+0.13 GB/device/microbatch). Kept as the recorded negative result.
    """
    if "model" not in mesh.axis_names or             embed.shape[0] % mesh.shape["model"] != 0:
        return embed.astype(cfg.dtype)[tokens]
    rows = embed.shape[0] // mesh.shape["model"]

    def local(tbl, tok):
        r = jax.lax.axis_index(TP)
        rel = tok - r * rows
        ok = (rel >= 0) & (rel < rows)
        x = jnp.take(tbl.astype(cfg.dtype), jnp.clip(rel, 0, rows - 1),
                     axis=0)
        x = x * ok[..., None].astype(cfg.dtype)
        return jax.lax.psum(x, TP)

    tok_spec = P(dp, None) if tokens.ndim == 2 else P(dp)
    out_spec = P(dp, *([None] * tokens.ndim))
    embed_dim_spec = None if not cfg.fsdp else dp_axes(mesh)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(TP, embed_dim_spec), tok_spec),
        out_specs=out_spec, check_vma=False)(embed, tokens)


# --------------------------------------------------------------------- #
# Forward (train / prefill)
# --------------------------------------------------------------------- #
def forward(params, tokens, cfg: LMConfig, mesh, *, positions=None):
    """tokens: i32[B, S] → logits f32[B, S, V] (TP-sharded on V)."""
    b, s = tokens.shape
    cst = _make_cst(mesh)
    dp = dp_axes(mesh)
    x = params["embed"].astype(cfg.dtype)[tokens]
    x = cst(x, dp, None, None)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def layer(x, lp):
        h = _rms_norm(x, lp["norm1"], cfg.norm_eps)
        q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        q = cst(q, dp, None, TP, None)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        attn = attention(q, k, v, positions, positions,
                         window=cfg.sliding_window,
                         q_block=cfg.q_block, k_block=cfg.k_block)
        attn = cst(attn, dp, None, TP)
        # (§Perf iteration 3 tried an optimization_barrier here to keep the
        # TP all-reduce in bf16 — refuted: the f32 ARs come from XLA's
        # AllReducePromotion pass, not operand dtype; see EXPERIMENTS.md.)
        x = x + attn @ lp["wo"]
        h2 = _rms_norm(x, lp["norm2"], cfg.norm_eps)
        ffn = (_moe_ffn(h2, lp, cfg, mesh) if cfg.moe
               else _dense_ffn(h2, lp, cfg, cst, dp))
        x = x + ffn
        x = cst(x, dp, None, None)
        return x, None

    body = layer
    if cfg.remat:
        body = jax.checkpoint(
            layer,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    x, _ = jax.lax.scan(body, x, params["layers"],
                        unroll=cfg.n_layers if cfg.unroll_layers else 1)
    x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"].astype(cfg.dtype)
    return cst(logits.astype(jnp.float32), dp, None, TP)


def loss_fn(params, batch, cfg: LMConfig, mesh):
    logits = forward(params, batch["tokens"], cfg, mesh)
    labels = batch["labels"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def make_train_step(cfg: LMConfig, mesh, optimizer):
    """train_step(params, opt_state, batch) → (params, opt_state, loss)."""

    def train_step(params, opt_state, batch):
        def lf(p, mb):
            return loss_fn(p, mb, cfg, mesh)

        if cfg.accum_steps > 1:
            a = cfg.accum_steps

            def split(x):
                return x.reshape(a, x.shape[0] // a, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                loss_acc, grad_acc = carry
                loss, grads = jax.value_and_grad(lf)(params, mb)
                grad_acc = jax.tree.map(
                    lambda ga, g: ga + g.astype(jnp.float32), grad_acc, grads)
                return (loss_acc + loss, grad_acc), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), micro)
            loss = loss / a
            grads = jax.tree.map(lambda g: g / a, grads)
        else:
            loss, grads = jax.value_and_grad(lf)(params, batch)
        params, opt_state = optimizer.apply(grads, opt_state, params)
        return params, opt_state, loss

    return train_step


# --------------------------------------------------------------------- #
# Serving: prefill + decode with (rolling) KV cache
# --------------------------------------------------------------------- #
def init_cache(cfg: LMConfig, batch: int, max_len: int):
    """Cache length = sliding window when set (rolling buffer), else max_len."""
    c = min(max_len, cfg.sliding_window or max_len)
    zeros = jnp.zeros((cfg.n_layers, batch, c, cfg.n_kv_heads, cfg.head_dim),
                      cfg.dtype)
    return dict(k=zeros, v=zeros,
                pos=jnp.zeros((batch, c), jnp.int32) - 1,
                t=jnp.zeros((), jnp.int32))


def cache_specs(cfg: LMConfig, mesh):
    dp = dp_axes(mesh)
    kv = P(None, dp, None, None, None)
    return dict(k=kv, v=kv, pos=P(dp, None), t=P())


def make_prefill(cfg: LMConfig, mesh, *, max_len: int | None = None):
    """prefill(params, tokens[B, S]) → (cache, logits[B, V] of last token).

    Fills the KV cache for subsequent decoding. Only the last position's
    logits are computed (never the [B, S, V] tensor — with a 256k vocab that
    would be petabytes at the 32k-prefill shape). Sliding-window configs
    keep the last W positions (rolling buffer layout, slot = pos mod W).
    ``max_len`` sizes the cache for subsequent decoding (defaults to the
    prompt length — the pure-prefill benchmark shape).
    """
    cst = _make_cst(mesh)
    dp = dp_axes(mesh)

    def prefill(params, tokens):
        b, s = tokens.shape
        c = min(max_len or s, cfg.sliding_window or max_len or s)
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        x = params["embed"].astype(cfg.dtype)[tokens]
        x = cst(x, dp, None, None)

        def layer(x, lp):
            h = _rms_norm(x, lp["norm1"], cfg.norm_eps)
            q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
            k = (h @ lp["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            v = (h @ lp["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
            q = cst(q, dp, None, TP, None)
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
            attn = attention(q, k, v, positions, positions,
                             window=cfg.sliding_window,
                             q_block=cfg.q_block, k_block=cfg.k_block)
            attn = cst(attn, dp, None, TP)
            x = x + attn @ lp["wo"]
            h2 = _rms_norm(x, lp["norm2"], cfg.norm_eps)
            ffn = (_moe_ffn(h2, lp, cfg, mesh) if cfg.moe
                   else _dense_ffn(h2, lp, cfg, cst, dp))
            x = x + ffn
            x = cst(x, dp, None, None)
            # rolling cache: last min(s, c) positions at slot = pos mod c
            if c <= s:
                shift = s % c
                kc = jnp.roll(k[:, -c:], shift, axis=1)
                vc = jnp.roll(v[:, -c:], shift, axis=1)
            else:                      # headroom for subsequent decode
                pad = ((0, 0), (0, c - s), (0, 0), (0, 0))
                kc = jnp.pad(k, pad)
                vc = jnp.pad(v, pad)
            return x, (kc, vc)

        body = layer
        if cfg.remat:
            body = jax.checkpoint(
                layer, policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)
        x, (ks, vs) = jax.lax.scan(
            body, x, params["layers"],
            unroll=cfg.n_layers if cfg.unroll_layers else 1)
        x = _rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        logits = (x @ params["lm_head"].astype(cfg.dtype))[:, 0]
        if c <= s:
            pos_cache = jnp.roll(jnp.arange(s - c, s, dtype=jnp.int32),
                                 s % c)
        else:
            pos_cache = jnp.concatenate(
                [jnp.arange(s, dtype=jnp.int32),
                 jnp.full((c - s,), -1, jnp.int32)])
        cache = dict(k=ks, v=vs,
                     pos=jnp.broadcast_to(pos_cache, (b, c)),
                     t=jnp.asarray(s, jnp.int32))
        return cache, logits.astype(jnp.float32)

    return prefill


def make_decode_step(cfg: LMConfig, mesh):
    """decode(params, cache, token[B]) → (cache, logits[B, V]).

    One new token against a cache of ``c`` slots; sliding-window configs use
    a rolling buffer (slot = t mod W): cost O(W) regardless of absolute
    position — the sub-quadratic long_500k path.
    """
    cst = _make_cst(mesh)
    dp = dp_axes(mesh)

    def decode(params, cache, token):
        b = token.shape[0]
        t = cache["t"]
        pos = jnp.full((b, 1), t, jnp.int32)
        x = params["embed"].astype(cfg.dtype)[token][:, None]
        x = cst(x, dp, None, None)
        c = cache["k"].shape[2]
        slot = t % c
        pos_cache = jax.lax.dynamic_update_slice(cache["pos"], pos, (0, slot))

        def layer(x, packed):
            lp, kc, vc = packed
            h = _rms_norm(x, lp["norm1"], cfg.norm_eps)
            q = (h @ lp["wq"]).reshape(b, 1, cfg.n_heads, cfg.head_dim)
            k = (h @ lp["wk"]).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
            v = (h @ lp["wv"]).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
            q = cst(q, dp, None, TP, None)
            q = _rope(q, pos, cfg.rope_theta)
            k = _rope(k, pos, cfg.rope_theta)
            kc = jax.lax.dynamic_update_slice(kc, k, (0, slot, 0, 0))
            vc = jax.lax.dynamic_update_slice(vc, v, (0, slot, 0, 0))
            attn = attention(q, kc, vc, pos, pos_cache,
                             window=cfg.sliding_window,
                             k_valid=pos_cache >= 0)
            attn = cst(attn, dp, None, TP)
            x = x + attn @ lp["wo"]
            h2 = _rms_norm(x, lp["norm2"], cfg.norm_eps)
            ffn = (_moe_ffn(h2, lp, cfg, mesh) if cfg.moe
                   else _dense_ffn(h2, lp, cfg, cst, dp))
            return x + ffn, (kc, vc)

        x, (k_new, v_new) = jax.lax.scan(
            layer, x, (params["layers"], cache["k"], cache["v"]),
            unroll=cfg.n_layers if cfg.unroll_layers else 1)
        x = _rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = (x @ params["lm_head"].astype(cfg.dtype))[:, 0]
        new_cache = dict(k=k_new, v=v_new, pos=pos_cache, t=t + 1)
        return new_cache, logits.astype(jnp.float32)

    return decode
