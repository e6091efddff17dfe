"""Coded weighted-accumulate Pallas kernel.

A worker's message to the master is  sum_i G[i,j] * g_i  over its
assigned task gradients; the master's decode is  sum_j w_j * m_j  over
worker messages.  Both are the same primitive: a weighted reduction of k
stacked flat gradient chunks,

    out[p] = sum_i w[i] * grads[i, p].

TPU adaptation: realized as a [1, k] @ [k, bp] MXU matvec per parameter
tile — the weights tile stays resident in VMEM while gradient chunks
stream HBM -> VMEM (arithmetic intensity 2 FLOP / 4 bytes: purely
bandwidth-bound, so the tiling maximizes the streaming run length bp).

The batched variant (``coded_accumulate_batched``) is the coded
all-reduce's on-device hot path: one device holds its local workers'
messages [k, P] and combines them against a whole [B, k] ensemble of
decode-weight rows (every step of a trace, or every mask of a
Monte-Carlo cell) in one launch — a [bb, bk] @ [bk, bp] MXU tile per
grid cell, messages streamed once and reused across the weight batch.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["coded_accumulate", "coded_accumulate_batched"]

# Mosaic contracts fp32 operands at reduced precision by default; decode
# weights and gradient messages are fp32 quantities, so ask for fp32
_FP32 = jax.lax.Precision.HIGHEST


def _acc_kernel(w_ref, g_ref, o_ref):
    w = w_ref[...]                           # [1, k]
    g = g_ref[...].astype(jnp.float32)       # [k, bp]
    o_ref[...] = jax.lax.dot_general(
        w, g, (((1,), (0,)), ((), ())),
        precision=_FP32,
        preferred_element_type=jnp.float32)  # [1, bp]


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def coded_accumulate(
    grads: jax.Array,             # [k, P] stacked flat task gradients
    weights: jax.Array,           # [k]
    *,
    bp: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """out = weights @ grads, tiled over the parameter dimension."""
    k, P = grads.shape
    bp = min(bp, P)
    np_ = math.ceil(P / bp)
    pad = np_ * bp - P
    g = jnp.pad(grads, ((0, 0), (0, pad))) if pad else grads
    w = weights.astype(jnp.float32)[None]    # [1, k]

    out = pl.pallas_call(
        _acc_kernel,
        grid=(np_,),
        in_specs=[
            pl.BlockSpec((1, k), lambda p: (0, 0)),
            pl.BlockSpec((k, bp), lambda p: (0, p)),
        ],
        out_specs=pl.BlockSpec((1, bp), lambda p: (0, p)),
        out_shape=jax.ShapeDtypeStruct((1, np_ * bp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(w, g)
    return out[0, :P]


def _acc_batch_kernel(w_ref, g_ref, o_ref, acc_ref, *, nk: int):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]                           # [bb, bk]
    g = g_ref[...].astype(jnp.float32)       # [bk, bp]
    acc_ref[...] += jax.lax.dot_general(
        w, g, (((1,), (0,)), ((), ())),
        precision=_FP32,
        preferred_element_type=jnp.float32)  # [bb, bp]

    @pl.when(i == nk - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


def _pad2(x, r, c):
    pr, pc = r - x.shape[0], c - x.shape[1]
    return jnp.pad(x, ((0, pr), (0, pc))) if pr or pc else x


@functools.partial(jax.jit, static_argnames=("bb", "bk", "bp", "interpret"))
def coded_accumulate_batched(
    grads: jax.Array,             # [k, P] stacked flat task gradients
    weights: jax.Array,           # [B, k] one weight row per mask / step
    *,
    bb: int = 128,
    bk: int = 512,
    bp: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """out = weights @ grads: every weight row decodes the same stack.

    [B, P] fp32.  Grid (batch, param-tile, k-tile) with the contracted
    k dimension innermost/sequential into an fp32 VMEM accumulator —
    the gradient stack streams HBM -> VMEM once per param tile and is
    reused by the whole weight-row block.
    """
    k, Pp = grads.shape
    B = weights.shape[0]
    bb, bk, bp = min(bb, B), min(bk, k), min(bp, Pp)
    nb, nk, np_ = map(math.ceil, (B / bb, k / bk, Pp / bp))
    g = _pad2(grads.astype(jnp.float32), nk * bk, np_ * bp)
    w = _pad2(weights.astype(jnp.float32), nb * bb, nk * bk)

    out = pl.pallas_call(
        functools.partial(_acc_batch_kernel, nk=nk),
        grid=(nb, np_, nk),
        in_specs=[
            pl.BlockSpec((bb, bk), lambda b, p, i: (b, i)),
            pl.BlockSpec((bk, bp), lambda b, p, i: (i, p)),
        ],
        out_specs=pl.BlockSpec((bb, bp), lambda b, p, i: (b, p)),
        out_shape=jax.ShapeDtypeStruct((nb * bb, np_ * bp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(w, g)
    return out[:B, :Pp]
