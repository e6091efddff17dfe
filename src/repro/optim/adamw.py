"""AdamW with global-norm clipping, fully hand-rolled (no optax), plus
ZeRO-1 sharding rules for the optimizer state.

The optimizer state is a pytree {mu, nu} mirroring params; under a mesh,
`opt_state_shardings` shards each moment like its parameter *plus* the
first replicated dimension over 'data' (ZeRO-1) when divisible.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import tracing
from ..dist.sharding import logical_to_pspec

__all__ = ["OptConfig", "init_opt_state", "adamw_update",
           "opt_state_shardings", "global_norm"]


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    schedule: str = "cosine"
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_ratio: float = 0.1
    decay_frac: float = 0.1
    # gradient compression (beyond-paper; composes with coding since the
    # decode is linear): 'none' | 'int8'
    compress: str = "none"


def init_opt_state(params) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "mu": jax.tree_util.tree_map(zeros, params),
        "nu": jax.tree_util.tree_map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2) for l in leaves))


def adamw_update(params, grads, state, cfg: OptConfig, lr: jax.Array
                 ) -> Tuple[Any, dict, dict]:
    """One AdamW step.  Returns (new_params, new_state, metrics)."""
    with jax.named_scope(tracing.OPTIMIZER):
        step = state["step"] + 1
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9)) \
            if cfg.clip_norm > 0 else 1.0

        if cfg.compress == "int8":
            from .compress import fake_quantize_int8
            grads = jax.tree_util.tree_map(fake_quantize_int8, grads)

        b1, b2 = cfg.b1, cfg.b2
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, g, mu, nu):
            g = g.astype(jnp.float32) * scale
            mu = b1 * mu + (1 - b1) * g
            nu = b2 * nu + (1 - b2) * (g * g)
            mhat = mu / bc1
            vhat = nu / bc2
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
            if cfg.weight_decay > 0 and p.ndim >= 2:  # no decay on norms/biases
                delta = delta + cfg.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), mu, nu

        flat_p, tdef = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_mu = jax.tree_util.tree_leaves(state["mu"])
        flat_nu = jax.tree_util.tree_leaves(state["nu"])
        out = [upd(p, g, m, n) for p, g, m, n in zip(flat_p, flat_g, flat_mu, flat_nu)]
        new_p = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
        new_mu = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
        new_nu = jax.tree_util.tree_unflatten(tdef, [o[2] for o in out])
        metrics = {"grad_norm": gnorm, "lr": lr}
        return new_p, {"mu": new_mu, "nu": new_nu, "step": step}, metrics


def opt_state_shardings(param_axes, abstract_params, mesh: Mesh,
                        zero1: bool = True):
    """NamedShardings for {mu, nu, step}.

    ZeRO-1: each moment inherits its parameter's PartitionSpec and, if a
    dimension is still replicated and divisible by the 'data' axis, that
    dimension is sharded over 'data' — optimizer memory scales down with
    the DP degree while params/grads stay DP-replicated.
    """
    data_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)

    def one(axes, aval):
        spec = list(logical_to_pspec(axes, aval.shape, mesh))
        spec += [None] * (len(aval.shape) - len(spec))
        if zero1 and "data" in mesh.axis_names:
            for i, (sp, dim) in enumerate(zip(spec, aval.shape)):
                if sp is None and dim % data_size == 0 and data_size > 1:
                    spec[i] = "data"
                    break
        return NamedSharding(mesh, P(*spec))

    is_axes = lambda t: isinstance(t, tuple) and all(
        isinstance(e, (str, type(None))) for e in t)
    moment = jax.tree_util.tree_map(one, param_axes, abstract_params,
                                    is_leaf=is_axes)
    return {
        "mu": moment,
        "nu": moment,
        "step": NamedSharding(mesh, P()),
    }
