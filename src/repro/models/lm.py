"""Decoder-only LM assembly: dense / MoE / hybrid (RG-LRU) / RWKV6 / VLM.

Layers are organized as repeating *pattern groups* (e.g. recurrentgemma's
("rec","rec","attn")); full groups are scanned with stacked parameters
(compile-time O(1) in depth), remainder layers are unrolled.  One code
path serves training, prefill and cached decode.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .. import tracing
from ..dist import constrain
from .config import ArchConfig
from .layers import KV_BLOCK, Q_BLOCK, cross_entropy, live_kv_blocks, norm
from .spec import ParamSpec
from . import blocks as B
from . import moe as moe_lib
from . import rglru as R
from . import rwkv6 as W

__all__ = ["lm_specs", "lm_forward", "lm_loss", "lm_prefill",
           "lm_decode_step", "init_lm_cache", "lm_cache_axes",
           "attn_live_blocks"]


# ------------------------- specs ---------------------------------------------

def _block_specs(kind: str, cfg: ArchConfig, prefix_shape=()) -> dict:
    if kind == "attn":
        return B.attn_block_specs(cfg, prefix_shape, with_moe=cfg.moe is not None)
    if kind == "rec":
        return R.rec_block_specs(cfg, prefix_shape)
    if kind == "rwkv":
        return W.rwkv_block_specs(cfg, prefix_shape)
    raise ValueError(kind)


def lm_specs(cfg: ArchConfig) -> dict:
    d, vp = cfg.d_model, cfg.padded_vocab
    specs: Dict[str, Any] = {
        "embed": ParamSpec((vp, d), ("vocab", None), init="embed", scale=0.02),
        "final_norm": B.norm_specs(cfg),
    }
    if not cfg.tie_embeddings:
        specs["head"] = ParamSpec((d, vp), (None, "vocab"))
    groups, rem = cfg.pattern_counts
    if cfg.scan_layers and groups > 0:
        specs["stack"] = {
            f"p{i}": _block_specs(kind, cfg, prefix_shape=(groups,))
            for i, kind in enumerate(cfg.block_pattern)
        }
    elif groups > 0:  # unrolled
        specs["unrolled"] = {
            f"l{g}_{i}": _block_specs(kind, cfg)
            for g in range(groups)
            for i, kind in enumerate(cfg.block_pattern)
        }
    specs["rem"] = {
        f"r{i}": _block_specs(kind, cfg)
        for i, kind in enumerate(cfg.block_pattern[:rem])
    }
    return specs


# ------------------------- caches --------------------------------------------

def _block_cache(kind: str, cfg: ArchConfig, batch: int, length: int, dtype,
                 i: int):
    """The cache of the block at pattern position ``i``."""
    if kind == "attn":
        return B.init_attn_cache(cfg, batch, length, dtype,
                                 window=cfg.window_at(i))
    if kind == "rec":
        return R.init_rec_cache(cfg, batch, dtype)
    if kind == "rwkv":
        return W.init_rwkv_cache(cfg, batch, dtype)
    raise ValueError(kind)


def init_lm_cache(cfg: ArchConfig, batch: int, length: int,
                  dtype=jnp.bfloat16) -> dict:
    groups, rem = cfg.pattern_counts
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.scan_layers and groups > 0:
        cache["stack"] = {
            f"p{i}": jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (groups,) + x.shape),
                _block_cache(kind, cfg, batch, length, dtype, i))
            for i, kind in enumerate(cfg.block_pattern)
        }
    elif groups > 0:
        cache["unrolled"] = {
            f"l{g}_{i}": _block_cache(kind, cfg, batch, length, dtype, i)
            for g in range(groups)
            for i, kind in enumerate(cfg.block_pattern)
        }
    cache["rem"] = {
        f"r{i}": _block_cache(kind, cfg, batch, length, dtype, i)
        for i, kind in enumerate(cfg.block_pattern[:rem])
    }
    return cache


def _attn_cache_axes(cfg: ArchConfig, stacked: bool):
    L = ("layers",) if stacked else ()
    kv_mode = ("batch", "seq_shard", "act_kv", None)
    return {"k": L + kv_mode, "v": L + kv_mode, "kpos": L + (None,)}


def _rec_cache_axes(stacked: bool):
    L = ("layers",) if stacked else ()
    return {"h": L + ("batch", "rnn"), "conv": L + ("batch", None, "rnn")}


def _rwkv_cache_axes(stacked: bool):
    L = ("layers",) if stacked else ()
    return {"state": L + ("batch", "act_heads", None, None),
            "x_tm": L + ("batch", None), "x_cm": L + ("batch", None)}


def lm_cache_axes(cfg: ArchConfig) -> dict:
    """Logical-axes tree matching init_lm_cache's structure."""
    def kind_axes(kind, stacked):
        if kind == "attn":
            return _attn_cache_axes(cfg, stacked)
        if kind == "rec":
            return _rec_cache_axes(stacked)
        return _rwkv_cache_axes(stacked)

    groups, rem = cfg.pattern_counts
    axes: Dict[str, Any] = {"pos": ()}
    if cfg.scan_layers and groups > 0:
        axes["stack"] = {f"p{i}": kind_axes(kind, True)
                         for i, kind in enumerate(cfg.block_pattern)}
    elif groups > 0:
        axes["unrolled"] = {f"l{g}_{i}": kind_axes(kind, False)
                            for g in range(groups)
                            for i, kind in enumerate(cfg.block_pattern)}
    axes["rem"] = {f"r{i}": kind_axes(kind, False)
                   for i, kind in enumerate(cfg.block_pattern[:rem])}
    return axes


# ------------------------- block dispatch -----------------------------------

def attn_live_blocks(cfg: ArchConfig, seq_len: int) -> int:
    """(q block, kv block) pairs that chunked attention computes in one
    pass over ``seq_len`` positions from 0, summed over the trunk's
    attention layers; 0 where attention does not take the chunked path."""
    if cfg.attn_impl != "xla_chunked" or seq_len <= Q_BLOCK:
        return 0
    groups, rem = cfg.pattern_counts
    kinds = list(enumerate(cfg.block_pattern)) * groups \
        + list(enumerate(cfg.block_pattern[:rem]))
    return sum(live_kv_blocks(seq_len, seq_len, Q_BLOCK, KV_BLOCK, True,
                              cfg.window_at(i))
               for i, kind in kinds if kind == "attn")


def _apply_block(kind: str, params, x, cfg: ArchConfig, positions, cache,
                 seq_mask=None, i: int = 0):
    """The block at pattern position ``i``: its window and RoPE are the
    position's own."""
    if kind == "attn":
        y, c, aux = B.attn_block_apply(
            params, x, cfg, positions=positions, causal=True,
            window=cfg.window_at(i), cache=cache,
            use_moe=cfg.moe is not None, seq_mask=seq_mask,
            yarn=cfg.yarn_at(i))
        return y, c, aux
    if seq_mask is not None or positions.ndim == 2:
        # recurrent state would absorb pad tokens; the serving engine
        # routes such archs through exact-length per-request prefill
        raise NotImplementedError(
            f"masked ragged prefill/decode supports attention blocks "
            f"only; got a {kind!r} block (see Model.supports_masked_prefill)")
    if kind == "rec":
        y, c = R.rec_block_apply(params, x, cfg, cache=cache)
        return y, c, jnp.zeros((), jnp.float32)
    if kind == "rwkv":
        y, c = W.rwkv_block_apply(params, x, cfg, cache=cache)
        return y, c, jnp.zeros((), jnp.float32)
    raise ValueError(kind)


def _remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


# ------------------------- forward -------------------------------------------

def _embed_inputs(params, cfg: ArchConfig, batch: dict) -> jax.Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    emb = params["embed"].astype(cdt)
    if cfg.frontend == "patches":
        tok = emb[batch["tokens"]]
        x = jnp.concatenate([batch["patches"].astype(cdt), tok], axis=1)
    else:
        x = emb[batch["tokens"]]
    return constrain(x, "batch", "seq", "embed")


def _run_blocks(params, cfg: ArchConfig, x, positions, caches=None,
                seq_mask=None):
    """Shared trunk: scan pattern groups + unrolled remainder.

    Returns (x, aux_sum, new_caches or None)."""
    groups, rem = cfg.pattern_counts
    pat = cfg.block_pattern
    decode = caches is not None
    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {} if decode else None

    if groups > 0 and cfg.scan_layers:
        stack_params = params["stack"]

        if decode:
            def group_body_dec(x, slices):
                p_slice, c_slice = slices
                aux_g = jnp.zeros((), jnp.float32)
                new_c = {}
                for i, kind in enumerate(pat):
                    x, c_out, aux = _apply_block(
                        kind, p_slice[f"p{i}"], x, cfg, positions,
                        c_slice[f"p{i}"], seq_mask, i)
                    new_c[f"p{i}"] = c_out
                    aux_g = moe_lib.add_stats(aux_g, aux)
                return x, (aux_g, new_c)

            x, (auxs, ncs) = jax.lax.scan(group_body_dec, x,
                                          (stack_params, caches["stack"]))
            new_caches["stack"] = ncs
        else:
            # a pattern of several blocks is rematerialized block by
            # block, so the backward pass holds one block's intermediates
            # at a time, not the whole group's
            remat_block = len(pat) > 1

            def group_body(x, p_slice):
                aux_g = jnp.zeros((), jnp.float32)
                for i, kind in enumerate(pat):
                    def blk_fn(p, x, kind=kind, i=i):
                        y, _, aux = _apply_block(kind, p, x, cfg, positions,
                                                 None, seq_mask, i)
                        return y, aux
                    if remat_block:
                        blk_fn = _remat(blk_fn, cfg)
                    x, aux = blk_fn(p_slice[f"p{i}"], x)
                    aux_g = moe_lib.add_stats(aux_g, aux)
                return x, aux_g

            body = group_body if remat_block else _remat(group_body, cfg)
            x, auxs = jax.lax.scan(body, x, stack_params)
        aux_total = moe_lib.add_stats(aux_total, moe_lib.sum_stats(auxs))
    elif groups > 0:
        for g in range(groups):
            for i, kind in enumerate(pat):
                key = f"l{g}_{i}"
                p_blk = params["unrolled"][key]
                if decode:
                    x, c_out, aux = _apply_block(kind, p_blk, x, cfg,
                                                 positions,
                                                 caches["unrolled"][key],
                                                 seq_mask, i)
                    new_caches.setdefault("unrolled", {})[key] = c_out
                else:
                    def blk_fn(p, x, kind=kind, i=i):
                        y, _, aux = _apply_block(kind, p, x, cfg, positions,
                                                 None, seq_mask, i)
                        return y, aux
                    fn = _remat(blk_fn, cfg) if cfg.remat != "none" else blk_fn
                    x, aux = fn(p_blk, x)
                    c_out = None
                aux_total = moe_lib.add_stats(aux_total, aux)

    for i, kind in enumerate(pat[:rem]):
        key = f"r{i}"
        c_in = caches["rem"][key] if decode else None
        x, c_out, aux = _apply_block(kind, params["rem"][key], x, cfg,
                                     positions, c_in, seq_mask, i)
        if decode:
            new_caches.setdefault("rem", {})[key] = c_out
        aux_total = moe_lib.add_stats(aux_total, aux)
    if decode and "rem" not in new_caches:
        new_caches["rem"] = {}
    return x, aux_total, new_caches


def _logits(params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    with jax.named_scope(tracing.HEAD_CE):
        x = norm(x, params["final_norm"], cfg.norm, io=cfg.norm_io)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        return constrain(logits, "batch", "seq", "vocab")


def lm_forward(params, cfg: ArchConfig, batch: dict) -> Tuple[jax.Array, jax.Array]:
    """Training/eval forward.  Returns (logits [B,S,Vp], aux): aux a
    scalar 0, or with expert layers their ``moe.MoEStats`` summed."""
    cdt = jnp.dtype(cfg.compute_dtype)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(cdt) if p.dtype == jnp.float32 and p.ndim >= 2 else p,
        params)
    x = _embed_inputs(params, cfg, batch)
    S = x.shape[1]
    positions = jnp.arange(S)
    x, aux, _ = _run_blocks(params, cfg, x, positions, caches=None)
    return _logits(params, cfg, x), aux


def _chunked_ce(params, cfg: ArchConfig, x: jax.Array, labels: jax.Array
                ) -> jax.Array:
    """Head + CE in sequence chunks of cfg.loss_chunk: the full
    [B, S, V] fp32 logits tensor is never materialized (each chunk's
    logits are checkpointed, recomputed in the backward pass).  Python
    loop, not lax.map — the dry-run's cost accounting must see every
    chunk (a while body is costed once).  Sec-Perf, command-r."""
    with jax.named_scope(tracing.HEAD_CE):
        head = (params["embed"].T if cfg.tie_embeddings
                else params["head"]).astype(x.dtype)

        def chunk_ce(xc, lc):
            xc = norm(xc, params["final_norm"], cfg.norm, io=cfg.norm_io)
            logits = jnp.einsum("bsd,dv->bsv", xc, head)
            return cross_entropy(logits, lc, cfg.vocab)

        chunk_ce = jax.checkpoint(chunk_ce)
        c = cfg.loss_chunk
        Sl = labels.shape[1]
        outs = [chunk_ce(x[:, i: i + c], labels[:, i: i + c])
                for i in range(0, Sl, c)]
        return jnp.concatenate(outs, axis=1)                 # [B, Sl] fp32


def lm_loss(params, cfg: ArchConfig, batch: dict) -> Tuple[jax.Array, dict]:
    """Coded-weighted loss.

    batch: tokens [B,St] (+patches for vlm), labels [B,Sl],
           loss_weight [B] (the gradient-coding decode weights folded per
           row; uniform 1/B when uncoded), loss_mask [B,Sl] optional.

    ``metrics["loss"]`` is the weighted CE.  With expert layers the aux
    loss is per row and weighted like the CE, loss = sum_r w_r (ce_r +
    0.01 aux_r), so each row's gradient is its own (the coding identity);
    metrics carry the layers' counters (``moe.aux_terms``).
    """
    labels = batch["labels"]
    Sl = labels.shape[1]
    if cfg.loss_chunk > 0:
        cdt = jnp.dtype(cfg.compute_dtype)
        cparams = jax.tree_util.tree_map(
            lambda p: p.astype(cdt)
            if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)
        x = _embed_inputs(cparams, cfg, batch)
        positions = jnp.arange(x.shape[1])
        x, aux, _ = _run_blocks(cparams, cfg, x, positions, caches=None)
        ce = _chunked_ce(cparams, cfg, x[:, -Sl:], labels)
    else:
        logits, aux = lm_forward(params, cfg, batch)
        logits = logits[:, -Sl:]  # vlm: loss only on the text suffix
        with jax.named_scope(tracing.HEAD_CE):
            ce = cross_entropy(logits, labels, cfg.vocab)  # [B, Sl] fp32
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(ce)
    row = (ce * mask).sum(-1) / jnp.maximum(mask.sum(-1), 1.0)
    weight = batch["loss_weight"].astype(jnp.float32)
    wloss = (row * weight).sum()
    aux, aux_metrics = moe_lib.aux_terms(aux, weight)
    loss = wloss + 0.01 * aux
    metrics = {
        "loss": wloss,
        "mean_ce": row.mean(),
        **aux_metrics,
    }
    return loss, metrics


# ------------------------- serving -------------------------------------------

def lm_prefill(params, cfg: ArchConfig, batch: dict, cache_len: int
               ) -> Tuple[jax.Array, dict]:
    """Process a prompt, returning (last-token logits, filled caches).

    ``batch["length_mask"]`` ([B, S] bool, True = real token) enables
    ragged LEFT-padded prompts: row i's real tokens sit right-aligned at
    ``tokens[i, S-len_i:]``.  Real tokens get per-row positions
    ``0..len_i-1`` (so RoPE and causal masking match an unpadded
    per-request prefill exactly), pads get distinct negative positions
    and are excluded from attention; the filled cache carries per-row
    ``pos`` [B] and per-row key validity, so subsequent decode steps are
    also per-row.  Attention-block archs only (recurrent state has no
    pad-skip; see Model.supports_masked_prefill).
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(cdt) if p.dtype == jnp.float32 and p.ndim >= 2 else p,
        params)
    x = _embed_inputs(params, cfg, batch)
    Bsz, S = x.shape[0], x.shape[1]
    mask = batch.get("length_mask")
    caches = init_lm_cache(cfg, Bsz, cache_len, cdt)
    if mask is None:
        positions = jnp.arange(S)
        x, _, new_caches = _run_blocks(params, cfg, x, positions,
                                       caches=caches)
        new_caches["pos"] = jnp.asarray(S, jnp.int32)
    else:
        mask = mask.astype(bool)
        lens = mask.sum(-1).astype(jnp.int32)                      # [B]
        positions = jnp.arange(S)[None, :] - (S - lens[:, None])   # [B, S]
        x, _, new_caches = _run_blocks(params, cfg, x, positions,
                                       caches=caches, seq_mask=mask)
        new_caches["pos"] = lens
    # left padding means the last real token is at index S-1 in every row
    logits = _logits(params, cfg, x[:, -1:])
    return logits[:, 0], new_caches


def lm_decode_step(params, cfg: ArchConfig, tokens: jax.Array, caches: dict
                   ) -> Tuple[jax.Array, dict]:
    """One decode step.  tokens [B, 1]; caches from prefill/init.

    ``caches["pos"]`` is a scalar (uniform batch) or [B] (per-row, after
    a masked ragged prefill); per-row positions route the attention
    blocks through the per-row ring-cache path.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(cdt) if p.dtype == jnp.float32 and p.ndim >= 2 else p,
        params)
    emb = params["embed"].astype(cdt)
    x = emb[tokens]
    x = constrain(x, "batch", None, "embed")
    pos = caches["pos"]
    if pos.ndim == 0:
        positions = pos[None] + jnp.arange(1)          # [1], shared
    else:
        positions = pos[:, None] + jnp.arange(1)       # [B, 1], per-row
    x, _, new_caches = _run_blocks(params, cfg, x, positions, caches=caches)
    new_caches["pos"] = pos + 1
    logits = _logits(params, cfg, x)
    return logits[:, 0], new_caches
