"""CodedAllReduce: shard_map coded gradient aggregation (docs/architecture.md §9).

After PR 1-2 the coded path still executed as a single-process
simulation — decode weights were folded into per-row loss weights and
one process computed the whole batch.  This module is the first place
the paper's Algorithm 1/2 dataflow runs on *actual devices*:

    workers  --(partition_workers)-->  devices      (ELL column packing)
    trace    --(sync policy)------->   masks [S, n]
    masks    --(DecodeEngine)------>   weights [S, n]   (ONE decode_batch)
    device d --(local grad)-------->   Σ_{j∈d} w_j Σ_i G[i,j] ∇L_i /(kT)
    devices  --(psum over 'workers')-> decoded gradient  (replicated)

Each of the n logical workers (columns of G) is pinned to a device lane;
a device owns ``lanes = ceil(n / D)`` workers (``-1``-padded when n is
not a multiple of the device count, so every device sees identical
shapes).  A straggler mask zeroes a worker's decode weight and with it
the whole device-lane contribution; decoding is the weighted ``psum``
over the 'workers' mesh axis.  The weights come from the cached batched
:class:`~repro.core.engine.DecodeEngine` — one ``decode_batch`` call per
trace, the PR 2 invariant, never a per-step decode loop.

Two aggregation surfaces:

  * :meth:`CodedAllReduce.value_and_grad` — the training path.  Wraps a
    loss function in shard_map: every device differentiates only its
    local rows (the decode-as-loss-reweighting identity of
    docs/architecture.md §2.1 restricted to the device's workers) and
    the psum of the local
    gradients IS the master decode.  Differentially tested against
    ``training.train_loop.explicit_master_decode_grads`` to fp64 in
    tests/test_coded_allreduce.py.
  * :meth:`CodedAllReduce.aggregate_messages_batch` — the explicit
    message path.  Per-worker coded gradient messages are combined
    on-device with the batched weighted-accumulate kernel
    (``kernels.coded_accumulate.coded_accumulate_batched``) and psum'd;
    ``sim.cluster.ClusterSim.run_distributed`` uses it to validate the
    E11 frontier errors against real multi-device execution.
  * :meth:`CodedAllReduce.aggregate_messages_fused` — the pipelined hot
    path.  For the one-step decoder the weights are rank-1 in the mask,
    so the decode rides the accumulate (``kernels.fused_decode_apply``):
    one pass over the worker messages, no weight ensemble.

The mesh may be multi-axis: the worker axis (``axis_name``) is manual
under shard_map while any remaining axes (data / model / FSDP) stay
GSPMD-automatic, so the coded aggregation composes with tensor-sharded
params (``sharding.make_coded_mesh`` builds such a mesh).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.assignment import CodedAssignment, build_assignment
from ..core.codes import GradientCode
from ..core.engine import DecodeEngine

__all__ = [
    "WORKER_AXIS",
    "DevicePartition",
    "partition_workers",
    "make_worker_mesh",
    "CodedAllReduce",
]

WORKER_AXIS = "workers"


# --------------------------------------------------------------------------
# worker -> device partition
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DevicePartition:
    """Static assignment of the n code columns to D device lanes.

    ``worker_ids[d, l]`` is the worker owned by lane l of device d, or
    -1 for a padding lane.  Workers are packed contiguously so the flat
    [worker, slot, row] batch layout of the pipeline reshapes into
    per-device microbatches with one gather.
    """

    n: int                      # logical workers (columns of G)
    n_devices: int              # mesh size D
    lanes: int                  # worker slots per device, ceil(n / D)
    worker_ids: np.ndarray      # [D, lanes] int32, -1 = padding lane

    @property
    def padded_n(self) -> int:
        return self.n_devices * self.lanes

    @property
    def lane_mask(self) -> np.ndarray:
        """[D, lanes] bool — True where the lane holds a real worker."""
        return self.worker_ids >= 0

    def scatter(self, per_worker: np.ndarray, fill: float = 0.0) -> np.ndarray:
        """[n, ...] per-worker array -> [D, lanes, ...]; pads get `fill`."""
        per_worker = np.asarray(per_worker)
        if per_worker.shape[0] != self.n:
            raise ValueError(f"leading dim {per_worker.shape[0]} != n={self.n}")
        out = np.full((self.padded_n,) + per_worker.shape[1:], fill,
                      dtype=per_worker.dtype)
        ids = self.worker_ids.reshape(-1)
        out[ids >= 0] = per_worker[ids[ids >= 0]]
        return out.reshape((self.n_devices, self.lanes) + per_worker.shape[1:])

    def gather(self, per_device: np.ndarray) -> np.ndarray:
        """[D, lanes, ...] -> [n, ...], dropping padding lanes (inverse
        of :meth:`scatter` for any fill value)."""
        per_device = np.asarray(per_device)
        flat = per_device.reshape((self.padded_n,) + per_device.shape[2:])
        ids = self.worker_ids.reshape(-1)
        out = np.empty((self.n,) + per_device.shape[2:], dtype=per_device.dtype)
        out[ids[ids >= 0]] = flat[ids >= 0]
        return out


def partition_workers(n: int, n_devices: int) -> DevicePartition:
    """Contiguous block partition of n workers over D devices.

    Handles every ragged case the tests exercise: n not a multiple of D
    (padding lanes), D = 1 (everything local), and D > n (trailing
    devices hold only padding and contribute exact zeros to the psum).
    """
    if n <= 0 or n_devices <= 0:
        raise ValueError(f"need n > 0 and n_devices > 0, got ({n}, {n_devices})")
    lanes = max(-(-n // n_devices), 1)
    ids = np.full((n_devices, lanes), -1, dtype=np.int32)
    flat = ids.reshape(-1)
    flat[:n] = np.arange(n, dtype=np.int32)
    return DevicePartition(n=n, n_devices=n_devices, lanes=lanes,
                           worker_ids=ids)


def make_worker_mesh(devices=None, axis_name: str = WORKER_AXIS) -> Mesh:
    """1-D mesh over the local devices; the coded all-reduce's world."""
    devs = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devs), (axis_name,))


# --------------------------------------------------------------------------
# the coded all-reduce
# --------------------------------------------------------------------------


class CodedAllReduce:
    """Coded data-parallel aggregation for one GradientCode on one mesh.

    Owns the worker->device partition and the shard_map'd aggregation
    functions.  The DecodeEngine is shared with (not owned by) the
    caller so the trainer / ClusterSim batch-call invariants hold on the
    engine they observe.
    """

    def __init__(self, code: GradientCode, *,
                 engine: Optional[DecodeEngine] = None,
                 assignment: Optional[CodedAssignment] = None,
                 mesh: Optional[Mesh] = None,
                 axis_name: str = WORKER_AXIS):
        self.code = code
        self.assignment = assignment if assignment is not None \
            else build_assignment(code)
        self.engine = engine if engine is not None else DecodeEngine(code)
        self.mesh = mesh if mesh is not None else make_worker_mesh(
            axis_name=axis_name)
        names = tuple(self.mesh.axis_names)
        # the worker axis may compose with data/model/FSDP axes: manual
        # over `axis_name`, GSPMD-automatic over everything else
        if axis_name in names:
            self.axis_name = axis_name
        elif len(names) == 1:
            self.axis_name = names[0]       # 1-D mesh: any axis name works
        else:
            raise ValueError(
                f"mesh axes {names} do not include the worker axis "
                f"{axis_name!r}; pass axis_name= to pick the coded axis of "
                f"a multi-axis mesh")
        self.auto_axes = frozenset(names) - {self.axis_name}
        self.partition = partition_workers(
            code.n, self.mesh.shape[self.axis_name])

    @classmethod
    def for_scheme(cls, scheme: str, n: int, *, s: int,
                   seed: int = 0, **kw) -> "CodedAllReduce":
        """Build the all-reduce for a registry scheme name at k = n.

        The registry-driven entry point the parametrized differential
        tests use: any family registered in core.registry (including
        sbm / expander) runs on the device mesh without this module
        knowing its name.
        """
        from ..core import registry

        return cls(registry.make(scheme, k=n, n=n, s=s, seed=seed), **kw)

    @property
    def n_devices(self) -> int:
        return self.partition.n_devices

    def _shard_map(self, fn, *, in_specs, out_specs):
        """shard_map manual over the worker axis only: any other mesh
        axes (data/model/FSDP) stay automatic, so GSPMD keeps sharding
        params and activations over them inside the worker-local body."""
        out = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                            out_specs=out_specs,
                            axis_names={self.axis_name}, check_vma=False)
        # partial-auto shard_map only lowers under jit, so multi-axis
        # meshes force it
        return jax.jit(out) if self.auto_axes else out

    # ------------------------------------------------------------------
    # per-step decode weights
    # ------------------------------------------------------------------

    def weights_for_masks(self, masks: np.ndarray, method: str = "onestep",
                          *, renorm: bool = True) -> np.ndarray:
        """[S, n] masks -> [S, n] decode weights in ONE decode_batch call.

        The whole trace decodes at once (the PR 2 ClusterSim invariant —
        ``engine.batch_calls`` advances by exactly 1); per-step lookup is
        then a row index.  ``renorm`` applies the trainer's
        exact-decode rescaling w <- w * k / sum(G @ w) per step, skipped
        for all-straggler rows where the denominator vanishes.
        """
        from ..core.decoding import exact_decode_renorm

        masks = np.asarray(masks, dtype=bool)
        if masks.ndim == 1:
            masks = masks[None]
        W = self.engine.decode_batch(masks, method).weights
        return exact_decode_renorm(self.code.G, W) if renorm else W

    def device_weights(self, w: np.ndarray) -> np.ndarray:
        """[n] decode weights -> [D, lanes] (zeros at padding lanes)."""
        return self.partition.scatter(np.asarray(w, dtype=np.float64))

    # ------------------------------------------------------------------
    # training path: shard_map'd loss gradient
    # ------------------------------------------------------------------

    def value_and_grad(self, loss_fn: Callable, *, has_aux: bool = True,
                       jit: bool = True) -> Callable:
        """shard_map'd ``(params, device_batch) -> ((loss, aux), grads)``.

        ``device_batch`` leaves lead with the device dimension D (from
        ``CodedDataPipeline.device_batch_for_step``); decode weights are
        already folded into each row's ``loss_weight``, restricted to
        the device's workers.  Every device runs one backward pass over
        its local rows and the gradients / loss are psum'd over the
        worker axis — the weighted-psum realization of the master
        decode.  Outputs are replicated on every device.

        Scalar aux metrics come back SUMMED over devices (psum); divide
        means (e.g. ``mean_ce``) by ``n_devices`` — every device holds
        the same padded row count so the mean of per-device means is the
        global mean.

        Additive regularizers beyond the per-row weighted sum (the MoE
        load-balance aux: loss = wloss + c*aux with the aux a LOCAL
        batch mean) would psum to c*D*aux_mean; when the aux dict
        carries the bare weighted loss under ``"loss"`` (the repo's
        loss_fn convention), the local objective is recomposed as
        ``wloss + (loss - wloss) * mine / n_real`` where ``mine`` zeroes
        the term on padding-only devices (whose rows are all zero
        tokens — their router statistics are garbage) and ``n_real``
        averages over the devices that hold real workers, so the psum'd
        regularizer matches the fused path.  Exact no-op when
        loss == wloss (dense models, the fp64 differential toys).
        """
        ax = self.axis_name
        # devices holding at least one real worker participate in the
        # additive-regularizer average; padding-only devices are masked.
        # The flag rides in as a worker-sharded input rather than an
        # axis_index lookup: partial-auto meshes can't lower PartitionId
        real_dev = self.partition.lane_mask.any(axis=1)     # [D] host-side
        n_real = max(int(real_dev.sum()), 1)
        flag = jnp.asarray(real_dev.astype(np.float32))     # [D]

        def local(params, dbatch, flag_d):
            batch = jax.tree_util.tree_map(lambda x: x[0], dbatch)
            if has_aux:
                def local_loss(p, b):
                    loss, aux = loss_fn(p, b)
                    base = aux.get("loss") if isinstance(aux, dict) else None
                    if base is not None:   # de-scale additive regularizers
                        loss = base + (loss - base) * flag_d[0] / n_real
                    return loss, aux

                (loss, aux), grads = jax.value_and_grad(
                    local_loss, has_aux=True)(params, batch)
            else:
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
                aux = ()
            loss = jax.lax.psum(loss, ax)
            grads = jax.lax.psum(grads, ax)
            aux = jax.tree_util.tree_map(lambda v: jax.lax.psum(v, ax), aux)
            return (loss, aux), grads

        inner = self._shard_map(local, in_specs=(P(), P(ax), P(ax)),
                                out_specs=P())

        def fn(params, dbatch):
            return inner(params, dbatch, flag)

        return jax.jit(fn) if jit else fn

    def batch_sharding(self) -> NamedSharding:
        """Sharding for device_batch leaves (leading dim D over workers)."""
        return NamedSharding(self.mesh, P(self.axis_name))

    def shard_batch(self, device_batch: dict) -> dict:
        """device_put a [D, ...]-leading batch onto the worker mesh."""
        sh = self.batch_sharding()
        return {k: jax.device_put(jnp.asarray(v), sh)
                for k, v in device_batch.items()}

    # ------------------------------------------------------------------
    # message path: explicit per-worker coded gradients
    # ------------------------------------------------------------------

    def aggregate_messages_batch(self, messages: np.ndarray,
                                 weights: np.ndarray, *,
                                 impl: str = "xla") -> np.ndarray:
        """Decode S steps of per-worker messages on the mesh: [S, P].

        ``messages[j]`` is worker j's coded partial Σ_i G[i,j] g_i
        (shape [n, P]); ``weights`` is the [S, n] decode-weight ensemble
        for S straggler masks.  Each device combines its local lanes
        with the batched weighted-accumulate kernel (`impl` selects
        xla / pallas / pallas_interpret) and the psum over the worker
        axis completes the decode.  Padding lanes carry zero weights so
        they contribute exact zeros.
        """
        from ..kernels import ops

        messages = np.asarray(messages)
        weights = np.atleast_2d(np.asarray(weights))
        if messages.shape[0] != self.code.n or weights.shape[1] != self.code.n:
            raise ValueError(
                f"messages {messages.shape} / weights {weights.shape} do not "
                f"match n={self.code.n}")
        part = self.partition
        msg = part.scatter(messages)                     # [D, L, P]
        wts = part.scatter(weights.T)                    # [D, L, S]
        ax = self.axis_name
        f64 = messages.dtype == np.float64 or weights.dtype == np.float64
        f64 = f64 and jax.config.jax_enable_x64

        def local(msg_d, w_d):
            m = msg_d[0]                                 # [L, P]
            w = w_d[0].T                                 # [S, L]
            if f64:   # dtype-preserving reference path (fp64 differential)
                out = w.astype(m.dtype) @ m
            else:
                out = ops.coded_accumulate_batched(
                    m, w, impl=impl, tiles=self.engine.tiles)
            return jax.lax.psum(out, ax)

        fn = self._shard_map(local, in_specs=(P(ax), P(ax)), out_specs=P())
        return np.asarray(fn(jnp.asarray(msg), jnp.asarray(wts)))

    def aggregate_messages_fused(self, messages: np.ndarray,
                                 masks: np.ndarray, *, renorm: bool = True,
                                 impl: str = "xla") -> np.ndarray:
        """One-step decode fused into the device-local accumulate: [S, P].

        Semantically ``aggregate_messages_batch(messages,
        weights_for_masks(masks, 'onestep', renorm=renorm))`` but the
        [S, n] weight ensemble is never materialized: the one-step
        weights are rank-1 in the mask (w = scale * m, see
        ``DecodeEngine.onestep_scales``), so each device contracts its
        raw 0/1 mask lanes against the local messages in a single
        ``kernels.fused_decode_apply`` pass and applies the per-step
        scale at emission.  The psum over the worker axis completes the
        decode.  Padding lanes scatter ``False`` masks -> exact zeros.
        """
        from ..kernels import ops

        messages = np.asarray(messages)
        masks = np.atleast_2d(np.asarray(masks, dtype=bool))
        if messages.shape[0] != self.code.n or masks.shape[1] != self.code.n:
            raise ValueError(
                f"messages {messages.shape} / masks {masks.shape} do not "
                f"match n={self.code.n}")
        part = self.partition
        scales = self.engine.onestep_scales(masks, renorm=renorm)
        msg = part.scatter(messages)                     # [D, L, P]
        mks = part.scatter(masks.T, fill=False)          # [D, L, S]
        ax = self.axis_name
        f64 = messages.dtype == np.float64 and jax.config.jax_enable_x64
        sc = jnp.asarray(scales if f64 else scales.astype(np.float32))

        def local(msg_d, m_d):
            m = msg_d[0]                                 # [L, P]
            mask_l = m_d[0].T                            # [S, L]
            if f64:   # dtype-preserving reference path (fp64 differential)
                out = (sc[:, None] * mask_l.astype(m.dtype)) @ m
            else:
                out = ops.fused_decode_apply(m, mask_l, sc, impl=impl,
                                             tiles=self.engine.tiles)
            return jax.lax.psum(out, ax)

        fn = self._shard_map(local, in_specs=(P(ax), P(ax)), out_specs=P())
        return np.asarray(fn(jnp.asarray(msg), jnp.asarray(mks)))

    def aggregate_messages(self, messages: np.ndarray, w: np.ndarray, *,
                           impl: str = "xla") -> np.ndarray:
        """Single-mask decode of per-worker messages -> [P]."""
        return self.aggregate_messages_batch(messages, np.asarray(w)[None],
                                             impl=impl)[0]

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"CodedAllReduce(code={self.code.name!r}, n={self.code.n}, "
                f"devices={self.n_devices}, lanes={self.partition.lanes}, "
                f"axis={self.axis_name!r})")
