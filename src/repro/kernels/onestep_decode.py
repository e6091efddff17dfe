"""One-step decoder (Algorithm 1) Pallas kernel.

    v = rho * A @ 1_r = rho * G @ mask      (mask = non-straggler indicator)

This is the paper's linear-time decoder: a masked row-sum over the
function-assignment matrix.  The kernel tiles G into [bk, bn] VMEM blocks
and reduces over the worker dimension sequentially in an fp32 VMEM
accumulator — it never materializes the submatrix A (the paper's
"streaming" property: Section 2, one-step decoding "allows us to avoid
putting the entire matrix A into memory").
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["onestep_decode"]

# Mosaic contracts fp32 operands at reduced precision by default; decode
# weights and gradient messages are fp32 quantities, so ask for fp32
_FP32 = jax.lax.Precision.HIGHEST


def _onestep_kernel(g_ref, m_ref, o_ref, acc_ref, *, nn: int, rho: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)       # [bk, bn]
    m = m_ref[...]                           # [1, bn]
    acc_ref[...] += jax.lax.dot_general(
        g, m, (((1,), (1,)), ((), ())),
        precision=_FP32,
        preferred_element_type=jnp.float32)  # [bk, 1]

    @pl.when(j == nn - 1)
    def _emit():
        o_ref[...] = rho * acc_ref[...]


@functools.partial(jax.jit, static_argnames=("rho", "bk", "bn", "interpret"))
def onestep_decode(
    G: jax.Array,                 # [k, n] assignment matrix
    mask: jax.Array,              # [n] bool/0-1 non-straggler indicator
    rho: float,
    *,
    bk: int = 512,
    bn: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """v = rho * G @ mask.  Returns [k] fp32."""
    k, n = G.shape
    bk = min(bk, k)
    bn = min(bn, n)
    nk = math.ceil(k / bk)
    nn = math.ceil(n / bn)
    pk, pn = nk * bk - k, nn * bn - n
    g = jnp.pad(G.astype(jnp.float32), ((0, pk), (0, pn))) \
        if (pk or pn) else G.astype(jnp.float32)
    m = jnp.pad(mask.astype(jnp.float32), (0, pn)) if pn else \
        mask.astype(jnp.float32)
    m = m[None]                              # [1, n]

    out = pl.pallas_call(
        functools.partial(_onestep_kernel, nn=nn, rho=float(rho)),
        grid=(nk, nn),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bk, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nk * bk, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(g, m)
    return out[:k, 0]
