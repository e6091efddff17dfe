"""Coded data pipeline.

Deterministic, stateless synthetic token streams: the tokens of (step,
task, row) are a pure function of (seed, step, task, row), so

  * every worker assigned task i generates *identical* data with zero
    communication (replication comes free),
  * resume-after-restart needs only the step counter (checkpointed),
  * elastic re-coding just changes the (worker -> task) table.

The stream is learnable (noisy affine-recurrence tokens) so end-to-end
convergence tests are meaningful, not pure noise.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from ..core.assignment import CodedAssignment

__all__ = ["PipelineConfig", "CodedDataPipeline"]


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    vocab: int
    seq_len: int
    rows_per_slot: int            # T: examples per task slot
    seed: int = 0
    mode: str = "markov"          # markov (learnable) | uniform


def _task_tokens(seed: int, step: int, task: int, rows: int, seq: int,
                 vocab: int, mode: str) -> np.ndarray:
    """Deterministic tokens for one task at one step: [rows, seq+1]."""
    rng = np.random.default_rng(
        np.random.SeedSequence([seed, step, task & 0x7FFFFFFF]))
    if mode == "uniform":
        return rng.integers(0, vocab, (rows, seq + 1), dtype=np.int64)
    # learnable stream: a GLOBAL affine recurrence over a small alphabet
    #   x_{t+1} = (a * x_t + c + eps_t) mod A,   eps in {0, 1}
    # (a, c) depend only on the seed, so the mapping is stationary across
    # steps/tasks and a small model visibly learns it within ~10 steps.
    A = min(64, vocab)
    g = np.random.default_rng(np.random.SeedSequence([seed]))
    a = int(g.integers(2, 8))
    c = int(g.integers(0, A))
    x0 = rng.integers(0, A, (rows, 1))
    noise = rng.integers(0, 2, (rows, seq + 1))
    out = np.empty((rows, seq + 1), dtype=np.int64)
    out[:, 0:1] = x0
    for t in range(1, seq + 1):
        out[:, t] = (a * out[:, t - 1] + c + noise[:, t]) % A
    return out


class CodedDataPipeline:
    """Produces physical batches laid out [worker, slot, row] -> flat B,
    and their fold to the held examples (``unique_batch_for_step``)."""

    def __init__(self, assignment: CodedAssignment, cfg: PipelineConfig):
        self.asg = assignment
        self.cfg = cfg
        self._lane_mask_cache: Dict[tuple, np.ndarray] = {}
        # fold tables (unique_batch_for_step): the physical rows that are
        # not padding, each one's held example (task order), and the first
        # replica of each held example.  Per assignment, so every re-code
        # (reshard_for) rebuilds them.
        uniq = assignment.unique_row_of_slot(cfg.rows_per_slot)
        self._fold_rows = np.flatnonzero(uniq >= 0)
        _, first, self._fold_dst = np.unique(
            uniq[self._fold_rows], return_index=True, return_inverse=True)
        self._fold_src = self._fold_rows[first]

    def reshard_for(self, assignment: CodedAssignment) -> "CodedDataPipeline":
        """Rebind the stream to a new assignment (elastic re-code / churn).

        Token content is a pure function of ``(cfg.seed, step, task)``, so
        resharding moves tasks between workers without dropping or
        double-counting any shard: the same logical examples reappear in
        the new layout, and a resharded pipeline at the same step yields
        the same per-task rows as an uninterrupted one.
        """
        return CodedDataPipeline(assignment, self.cfg)

    @property
    def physical_batch(self) -> int:
        return self.asg.n * self.asg.slots * self.cfg.rows_per_slot

    @property
    def unique_examples(self) -> int:
        return self.asg.k * self.cfg.rows_per_slot

    def batch_for_step(self, step: int, decode_w: np.ndarray
                       ) -> Dict[str, np.ndarray]:
        """Materialize the physical batch + coded loss weights for a step.

        decode_w: (n,) decode weights for this step's straggler mask.
        """
        cfg, asg = self.cfg, self.asg
        T, S, V = cfg.rows_per_slot, cfg.seq_len, cfg.vocab
        B = self.physical_batch
        tokens = np.zeros((B, S), dtype=np.int32)
        labels = np.zeros((B, S), dtype=np.int32)

        # generate each unique task once, then fan out to its replicas
        cache: Dict[int, np.ndarray] = {}
        row = 0
        for j in range(asg.n):
            for t in range(asg.slots):
                task = int(asg.task_ids[j, t])
                if task >= 0:
                    if task not in cache:
                        cache[task] = _task_tokens(cfg.seed, step, task, T, S,
                                                   V, cfg.mode)
                    data = cache[task]
                    tokens[row : row + T] = data[:, :-1]
                    labels[row : row + T] = data[:, 1:]
                row += T

        weights = self.asg.row_weights(decode_w, T)
        return {"tokens": tokens, "labels": labels, "loss_weight": weights}

    def unique_batch_for_step(self, step: int, decode_w: np.ndarray
                              ) -> Dict[str, np.ndarray]:
        """The physical batch folded to one row per held example.

        Rows are (held task, row-in-slot) in task order; tasks no worker
        holds and padding slots are absent.  Tokens and labels come from
        a task's first replica (replicas are equal by construction), the
        loss weight is the float64 sum of its replicas' row weights,
        sum_j w_j G[i,j] / (k*T) — so the weighted loss and its gradient
        are the physical batch's (docs/architecture.md §2.1), from each
        example computed once.  Folds what ``batch_for_step`` returns.
        """
        b = self.batch_for_step(step, decode_w)
        src = self._fold_src
        w = np.bincount(self._fold_dst,
                        weights=b["loss_weight"][self._fold_rows],
                        minlength=src.size)
        return {"tokens": b["tokens"][src], "labels": b["labels"][src],
                "loss_weight": w}

    def device_batch_for_step(self, step: int, decode_w: np.ndarray,
                              partition) -> Dict[str, np.ndarray]:
        """The coded batch re-laid-out as per-device microbatches.

        partition: a dist.coded_allreduce.DevicePartition for this
        assignment's n workers.  Every leaf leads with the device
        dimension D; each device's microbatch holds the rows of its
        ``lanes`` workers in lane order (R = lanes * slots * T rows per
        device).  Padding lanes (n not a multiple of D) carry zero
        tokens with zero loss_weight, so all devices see identical
        shapes and contribute exact zeros to the coded psum.
        """
        if partition.n != self.asg.n:
            raise ValueError(f"partition has n={partition.n} workers, "
                             f"assignment has n={self.asg.n}")
        flat = self.batch_for_step(step, decode_w)
        rpw = self.asg.slots * self.cfg.rows_per_slot
        D, L = partition.n_devices, partition.lanes
        ids = partition.worker_ids                          # [D, L]
        src = np.where(ids >= 0, ids, 0)[..., None] * rpw + np.arange(rpw)
        src = src.reshape(-1)                               # [D*L*rpw]
        row_ok = np.repeat(partition.lane_mask.reshape(-1), rpw)
        out: Dict[str, np.ndarray] = {}
        for name, x in flat.items():
            v = x[src]
            v[~row_ok] = 0
            out[name] = v.reshape((D, L * rpw) + x.shape[1:])
        if not partition.lane_mask.all():
            # ragged n/D: zero the padding-lane rows out of the models'
            # per-row CE (they already carry zero loss_weight, but the
            # mean_ce metric would otherwise average in garbage rows —
            # the trainer rescales by padded_n/n to undo the dilution).
            # Step-independent -> built once per (partition, seq) shape.
            seq = flat["labels"].shape[1]
            key = (D, L, partition.n, rpw, seq)
            lm = self._lane_mask_cache.get(key)
            if lm is None:
                lm = np.ascontiguousarray(np.broadcast_to(
                    row_ok.reshape(D, L * rpw)[..., None],
                    (D, L * rpw, seq)), dtype=np.float32)
                self._lane_mask_cache[key] = lm
            out["loss_mask"] = lm
        return out

    def uncoded_batch_for_step(self, step: int) -> Dict[str, np.ndarray]:
        """The k*T unique examples with uniform mean weights (baseline)."""
        cfg, asg = self.cfg, self.asg
        T, S, V = cfg.rows_per_slot, cfg.seq_len, cfg.vocab
        k = asg.k
        tokens = np.zeros((k * T, S), dtype=np.int32)
        labels = np.zeros((k * T, S), dtype=np.int32)
        for task in range(k):
            data = _task_tokens(cfg.seed, step, task, T, S, V, cfg.mode)
            tokens[task * T : (task + 1) * T] = data[:, :-1]
            labels[task * T : (task + 1) * T] = data[:, 1:]
        w = np.full((k * T,), 1.0 / (k * T), dtype=np.float32)
        return {"tokens": tokens, "labels": labels, "loss_weight": w}

    def state(self) -> dict:
        return {"seed": self.cfg.seed}  # stateless beyond the step counter
