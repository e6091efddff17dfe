"""Residual blocks: attention (+MLP/MoE), cross-attention; param specs and
apply functions with a uniform (params, x, cache) -> (y, cache) interface.

All blocks are cache-polymorphic: cache=None means full-sequence training
/ prefill-without-cache; a cache dict means single-or-multi-token decode
with static shapes (ring buffers for windowed attention).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import tracing
from ..dist import constrain
from .config import ArchConfig
from .layers import attention, mlp, norm, rope
from .spec import ParamSpec
from . import moe as moe_lib

__all__ = [
    "norm_specs", "attn_block_specs", "cross_block_specs",
    "attn_block_apply", "cross_block_apply",
    "init_attn_cache", "init_cross_cache",
]


def _p(prefix_shape):
    """Leading logical axes for an optional stacked-layer prefix."""
    return tuple("layers" for _ in prefix_shape)


def norm_specs(cfg: ArchConfig, prefix_shape=()) -> dict:
    d = cfg.d_model
    axes = _p(prefix_shape) + (None,)
    out = {"scale": ParamSpec(prefix_shape + (d,), axes,
                              init="zeros" if cfg.norm == "rmsnorm" else "ones")}
    if cfg.norm == "layernorm":
        out["bias"] = ParamSpec(prefix_shape + (d,), axes, init="zeros")
    return out


def mlp_specs(cfg: ArchConfig, prefix_shape=(), d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    L = _p(prefix_shape)
    out = {}
    if cfg.act in ("swiglu", "geglu"):
        out["wg"] = ParamSpec(prefix_shape + (d, f), L + (None, "mlp"))
        out["wi"] = ParamSpec(prefix_shape + (d, f), L + (None, "mlp"))
    else:
        out["wi"] = ParamSpec(prefix_shape + (d, f), L + (None, "mlp"))
        out["bi"] = ParamSpec(prefix_shape + (f,), L + ("mlp",), init="zeros")
    out["wo"] = ParamSpec(prefix_shape + (f, d), L + ("mlp", None))
    if cfg.act == "gelu":
        out["bo"] = ParamSpec(prefix_shape + (d,), L + (None,), init="zeros")
    return out


def attn_specs(cfg: ArchConfig, prefix_shape=()) -> dict:
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    L = _p(prefix_shape)
    out = {
        "wq": ParamSpec(prefix_shape + (d, qd), L + (None, "qkv")),
        "wk": ParamSpec(prefix_shape + (d, kvd), L + (None, "kv")),
        "wv": ParamSpec(prefix_shape + (d, kvd), L + (None, "kv")),
        "wo": ParamSpec(prefix_shape + (qd, d), L + ("qkv", None)),
    }
    if cfg.qkv_bias:
        out["bq"] = ParamSpec(prefix_shape + (qd,), L + ("qkv",), init="zeros")
        out["bk"] = ParamSpec(prefix_shape + (kvd,), L + ("kv",), init="zeros")
        out["bv"] = ParamSpec(prefix_shape + (kvd,), L + ("kv",), init="zeros")
    return out


def attn_block_specs(cfg: ArchConfig, prefix_shape=(), with_moe: bool = False) -> dict:
    specs = {
        "ln1": norm_specs(cfg, prefix_shape),
        "attn": attn_specs(cfg, prefix_shape),
        "ln2": norm_specs(cfg, prefix_shape),
    }
    if with_moe and cfg.moe is not None:
        specs["moe"] = moe_lib.moe_specs(cfg, prefix_shape)
    else:
        specs["mlp"] = mlp_specs(cfg, prefix_shape)
    return specs


def cross_block_specs(cfg: ArchConfig, prefix_shape=()) -> dict:
    return {"ln": norm_specs(cfg, prefix_shape), "attn": attn_specs(cfg, prefix_shape)}


# ----------------------------- caches ----------------------------------------

def init_attn_cache(cfg: ArchConfig, batch: int, length: int, dtype) -> dict:
    """Static-shape KV cache; windowed layers use a ring buffer of size
    min(window, length)."""
    W = min(cfg.local_window, length) if cfg.local_window else length
    kv = cfg.n_kv
    return {
        "k": jnp.zeros((batch, W, kv, cfg.d_head), dtype),
        "v": jnp.zeros((batch, W, kv, cfg.d_head), dtype),
        "kpos": jnp.full((W,), -1, jnp.int32),  # absolute positions (-1 empty)
    }


def init_cross_cache(cfg: ArchConfig, batch: int, enc_len: int, dtype) -> dict:
    return {
        "k": jnp.zeros((batch, enc_len, cfg.n_kv, cfg.d_head), dtype),
        "v": jnp.zeros((batch, enc_len, cfg.n_kv, cfg.d_head), dtype),
    }


# ----------------------------- apply -----------------------------------------

def _project_qkv(p: dict, x: jax.Array, cfg: ArchConfig):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("bsd,de->bse", x, p["wk"])
    v = jnp.einsum("bsd,de->bse", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
    k = k.reshape(B, S, cfg.n_kv, cfg.d_head)
    v = v.reshape(B, S, cfg.n_kv, cfg.d_head)
    return q, k, v


def attn_block_apply(
    params: dict,
    x: jax.Array,
    cfg: ArchConfig,
    *,
    positions: jax.Array,            # [S] absolute positions of x, or
                                     # [B, S] per-row (ragged serving)
    causal: bool = True,
    window: int = 0,
    cache: Optional[dict] = None,
    use_moe: bool = False,
    seq_mask: Optional[jax.Array] = None,  # [B, S] bool: True = real token
) -> Tuple[jax.Array, Optional[dict], jax.Array]:
    """Pre-norm residual block. Returns (y, new_cache, aux_loss).

    With ``seq_mask`` (masked ragged prefill) or 2-D ``positions``
    (decode after one), pad/invalid keys carry negative positions: they
    are excluded from attention and land in unused ring slots whose
    ``kpos`` stays < 0 — the same "empty" convention the ring cache
    already uses — so later decode steps never attend to them.
    """
    with jax.named_scope(tracing.ATTENTION):
        x, new_cache = _attn_residual(params, x, cfg, positions, causal,
                                      window, cache, seq_mask)
    with jax.named_scope(tracing.MLP):
        h2 = norm(x, params["ln2"], cfg.norm, io=cfg.norm_io)
        aux = jnp.zeros((), jnp.float32)
        if use_moe:
            ff, aux = moe_lib.moe_apply(params["moe"], h2, cfg)
        else:
            ff = mlp(h2, params["mlp"], cfg.act)
        x = x + ff
    return x, new_cache, aux


def _attn_residual(params: dict, x: jax.Array, cfg: ArchConfig,
                   positions: jax.Array, causal: bool, window: int,
                   cache: Optional[dict], seq_mask: Optional[jax.Array]
                   ) -> Tuple[jax.Array, Optional[dict]]:
    """x + attention(ln1(x)) and the updated cache (attn_block_apply)."""
    B, S, _ = x.shape
    ragged = positions.ndim == 2
    h = norm(x, params["ln1"], cfg.norm, io=cfg.norm_io)
    q, k, v = _project_qkv(params["attn"], h, cfg)
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", None, "act_heads", None)
    k = constrain(k, "batch", None, "act_kv", None)

    if cache is None:
        if ragged or seq_mask is not None:
            out = attention(q, k, v, causal=causal, window=window,
                            softcap=cfg.logit_softcap, qpos=positions,
                            kpos=positions, kv_valid=seq_mask,
                            impl=cfg.attn_impl)
        else:
            out = attention(q, k, v, causal=causal, window=window,
                            softcap=cfg.logit_softcap, q_offset=positions[0],
                            impl=cfg.attn_impl)
        new_cache = None
    elif S == 1 and ragged:
        # per-row cached decode (after a masked ragged prefill): each row
        # inserts at its own position; kpos is per-row [B, W]
        W = cache["k"].shape[1]
        bidx = jnp.arange(B)
        slot = (positions[:, 0] % W).astype(jnp.int32)
        ck = cache["k"].at[bidx, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, slot].set(v[:, 0].astype(cache["v"].dtype))
        kpos = jnp.broadcast_to(cache["kpos"], (B, W))
        kpos = kpos.at[bidx, slot].set(positions[:, 0].astype(jnp.int32))
        out = attention(q, ck, cv, causal=causal, window=window,
                        softcap=cfg.logit_softcap, qpos=positions,
                        kpos=kpos, kv_valid=kpos >= 0, impl="xla_naive")
        new_cache = {"k": ck, "v": cv, "kpos": kpos}
    elif S == 1:  # cached decode: ring-buffer insert + attend over buffer
        W = cache["k"].shape[1]
        slot = positions % W
        ck = cache["k"].at[:, slot].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[:, slot].set(v.astype(cache["v"].dtype))
        kpos = cache["kpos"].at[slot].set(positions.astype(jnp.int32))
        out = attention(q, ck, cv, causal=causal, window=window,
                        softcap=cfg.logit_softcap, q_offset=positions[0],
                        kpos=kpos, kv_valid=kpos >= 0, impl="xla_naive")
        new_cache = {"k": ck, "v": cv, "kpos": kpos}
    elif ragged or seq_mask is not None:
        # masked ragged prefill: full attention over valid keys only,
        # then per-row tail write (pads keep kpos < 0 = invalid slots)
        out = attention(q, k, v, causal=causal, window=window,
                        softcap=cfg.logit_softcap, qpos=positions,
                        kpos=positions, kv_valid=seq_mask,
                        impl=cfg.attn_impl)
        W = cache["k"].shape[1]
        take = min(W, S)
        pos_tail = positions[:, -take:].astype(jnp.int32)   # [B, take]
        slot = pos_tail % W
        bidx = jnp.arange(B)[:, None]
        ck = cache["k"].at[bidx, slot].set(
            k[:, -take:].astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, slot].set(
            v[:, -take:].astype(cache["v"].dtype))
        kpos = jnp.broadcast_to(cache["kpos"], (B, W))
        kpos = kpos.at[bidx, slot].set(pos_tail)
        new_cache = {"k": ck, "v": cv, "kpos": kpos}
    else:  # prefill: full attention, then write the tail into the cache
        out = attention(q, k, v, causal=causal, window=window,
                        softcap=cfg.logit_softcap, q_offset=positions[0],
                        impl=cfg.attn_impl)
        W = cache["k"].shape[1]
        take = min(W, S)
        pos_tail = positions[-take:]
        slot = pos_tail % W
        ck = cache["k"].at[:, slot].set(k[:, -take:].astype(cache["k"].dtype))
        cv = cache["v"].at[:, slot].set(v[:, -take:].astype(cache["v"].dtype))
        kpos = cache["kpos"].at[slot].set(pos_tail.astype(jnp.int32))
        new_cache = {"k": ck, "v": cv, "kpos": kpos}

    out = out.reshape(B, S, cfg.q_dim)
    x = x + jnp.einsum("bse,ed->bsd", out, params["attn"]["wo"])
    return x, new_cache


def cross_block_apply(
    params: dict,
    x: jax.Array,
    cross_cache: dict,               # precomputed encoder K/V
    cfg: ArchConfig,
) -> jax.Array:
    """Cross-attention residual block (encoder-decoder)."""
    B, S, _ = x.shape
    h = norm(x, params["ln"], cfg.norm, io=cfg.norm_io)
    q = jnp.einsum("bsd,de->bse", h, params["attn"]["wq"])
    if cfg.qkv_bias:
        q = q + params["attn"]["bq"]
    q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
    out = attention(q, cross_cache["k"], cross_cache["v"], causal=False,
                    impl="xla_naive")
    out = out.reshape(B, S, cfg.q_dim)
    return x + jnp.einsum("bse,ed->bsd", out, params["attn"]["wo"])


def make_cross_cache(params: dict, enc_out: jax.Array, cfg: ArchConfig) -> dict:
    """Project encoder output once into cross K/V (whisper-style)."""
    B, T, _ = enc_out.shape
    k = jnp.einsum("btd,de->bte", enc_out, params["attn"]["wk"])
    v = jnp.einsum("btd,de->bte", enc_out, params["attn"]["wv"])
    if cfg.qkv_bias:
        k, v = k + params["attn"]["bk"], v + params["attn"]["bv"]
    return {"k": k.reshape(B, T, cfg.n_kv, cfg.d_head),
            "v": v.reshape(B, T, cfg.n_kv, cfg.d_head)}
