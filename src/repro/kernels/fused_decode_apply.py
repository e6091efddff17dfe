"""Fused decode-apply Pallas kernel: masks -> decoded gradients, one pass.

The weights-then-psum composition decodes in two passes: DecodeEngine
materializes the [B, n] weight ensemble (plus its error reduction), then
``coded_accumulate_batched`` contracts it against the worker messages.
For the one-step decoder the weights are a rank-1 function of the mask
(w_b = s_b * m_b, with s_b the per-mask rho or its renormalized form),
so the decode can ride the accumulate itself:

    out[b, p] = s_b * sum_j m[b, j] * msgs[j, p]

One [bb, bl] @ [bl, bp] MXU tile per grid cell — the mask tile plays
the role of the weight tile and the scalar scale is applied once at
emission, so the [B, n] weight ensemble is never built and the messages
stream HBM -> VMEM exactly once per param tile (same arithmetic
intensity as coded_accumulate_batched, one fewer pass over the batch).

The contracted worker dimension is innermost/sequential into an fp32
VMEM accumulator; scales ride along as a [bb, 1] block exactly like the
rhos of ``batched_decode._onestep_batch_kernel``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_decode_apply"]

# Mosaic contracts fp32 operands at reduced precision by default; decode
# weights and gradient messages are fp32 quantities, so ask for fp32
_FP32 = jax.lax.Precision.HIGHEST


def _fused_kernel(m_ref, g_ref, s_ref, o_ref, acc_ref, *, nl: int):
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m = m_ref[...]                           # [bb, bl] mask tile (0/1 f32)
    g = g_ref[...].astype(jnp.float32)       # [bl, bp] message tile
    acc_ref[...] += jax.lax.dot_general(
        m, g, (((1,), (0,)), ((), ())),
        precision=_FP32,
        preferred_element_type=jnp.float32)  # [bb, bp]

    @pl.when(i == nl - 1)
    def _emit():
        o_ref[...] = acc_ref[...] * s_ref[...]   # [bb, 1] scale broadcast


def _pad2(x, r, c):
    pr, pc = r - x.shape[0], c - x.shape[1]
    return jnp.pad(x, ((0, pr), (0, pc))) if pr or pc else x


@functools.partial(jax.jit, static_argnames=("bb", "bl", "bp", "interpret"))
def fused_decode_apply(
    messages: jax.Array,          # [L, P] per-worker coded messages
    masks: jax.Array,             # [B, L] bool/0-1 non-straggler masks
    scales: jax.Array,            # [B] per-mask one-step decode scale
    *,
    bb: int = 128,
    bl: int = 512,
    bp: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """out[b] = scales[b] * (masks[b] @ messages).  [B, P] fp32."""
    L, P = messages.shape
    B = masks.shape[0]
    bb, bl, bp = min(bb, B), min(bl, L), min(bp, P)
    nb, nl, np_ = map(math.ceil, (B / bb, L / bl, P / bp))
    g = _pad2(messages.astype(jnp.float32), nl * bl, np_ * bp)
    m = _pad2(masks.astype(jnp.float32), nb * bb, nl * bl)
    s = _pad2(scales.astype(jnp.float32)[:, None], nb * bb, 1)

    out = pl.pallas_call(
        functools.partial(_fused_kernel, nl=nl),
        grid=(nb, np_, nl),
        in_specs=[
            pl.BlockSpec((bb, bl), lambda b, p, i: (b, i)),
            pl.BlockSpec((bl, bp), lambda b, p, i: (i, p)),
            pl.BlockSpec((bb, 1), lambda b, p, i: (b, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bp), lambda b, p, i: (b, p)),
        out_shape=jax.ShapeDtypeStruct((nb * bb, np_ * bp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(m, g, s)
    return out[:B, :P]
