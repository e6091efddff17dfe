"""What a cell feeds the trainer, made from its traffic file and the
seed, and the plain arithmetic the reference needs to follow a step.

Copies, so that the yardstick stays put while the program changes:

* the Pareto straggler latencies (``runtime.straggler.DeadlineStragglers``
  as ``sim.traces.make_trace("pareto")`` draws them): latency = base +
  tail_scale * (Lomax(alpha) + 1);
* the deadline sync policy (``sim.cluster.DeadlinePolicy``): a worker
  arrived iff its latency is at most the deadline;
* the coded data stream (``data.pipeline._task_tokens``, markov mode):
  the rows of (step, task) are a pure function of (data seed, step,
  task);
* the decoders' defining equations (``core.decoding``): one-step
  w = k / (r s) on the arrived workers, optimal = least squares of
  G_A x = 1, then the exact-decode rescaling sum(G w) = k.
"""

from __future__ import annotations

import numpy as np


def latencies(traffic: dict, seed: int) -> np.ndarray:
    """[steps, n] worker latencies (seconds) of the cell's trace."""
    tr = traffic["trace"]
    if tr["kind"] != "pareto":
        raise ValueError(f"trace kind {tr['kind']!r} is not 'pareto'")
    n = traffic["code"]["n"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x7A11])
    tail = rng.pareto(tr["alpha"], (tr["steps"], n)) + 1.0
    return tr["base"] + tr["tail_scale"] * tail


def arrived(traffic: dict, lat_row: np.ndarray) -> np.ndarray:
    """Deadline policy: the workers whose latency is within it."""
    pol = traffic["sync_policy"]
    if pol["kind"] != "deadline":
        raise ValueError(f"sync policy {pol['kind']!r} is not 'deadline'")
    return np.asarray(lat_row) <= pol["deadline"]


def task_tokens(data_seed: int, step: int, task: int, rows: int, seq: int,
                vocab: int) -> np.ndarray:
    """[rows, seq + 1] tokens of one task at one step (markov stream:
    x_{t+1} = (a x_t + c + eps_t) mod A over A = min(64, vocab) symbols,
    (a, c) drawn from the data seed, eps in {0, 1})."""
    rng = np.random.default_rng(
        np.random.SeedSequence([data_seed, step, task & 0x7FFFFFFF]))
    A = min(64, vocab)
    g = np.random.default_rng(np.random.SeedSequence([data_seed]))
    a = int(g.integers(2, 8))
    c = int(g.integers(0, A))
    x0 = rng.integers(0, A, (rows, 1))
    noise = rng.integers(0, 2, (rows, seq + 1))
    out = np.empty((rows, seq + 1), dtype=np.int64)
    out[:, 0:1] = x0
    for t in range(1, seq + 1):
        out[:, t] = (a * out[:, t - 1] + c + noise[:, t]) % A
    return out


def decode_weights(G: np.ndarray, mask: np.ndarray, decoder: str,
                   s: int) -> np.ndarray:
    """[n] decode weights of one arrival mask (zero where a worker did
    not arrive), rescaled so that sum(G w) = k (left as they are when
    that sum vanishes)."""
    G = np.asarray(G, dtype=np.float64)
    mask = np.asarray(mask, dtype=bool)
    k, n = G.shape
    r = int(mask.sum())
    w = np.zeros(n)
    if r == 0:
        return w
    if decoder == "onestep":
        w[mask] = k / (r * s)
    elif decoder == "optimal":
        w[mask], *_ = np.linalg.lstsq(G[:, mask], np.ones(k), rcond=None)
    else:
        raise ValueError(f"decoder {decoder!r} not in ('onestep', 'optimal')")
    tot = (G @ w).sum()
    return w * (k / tot) if tot > 1e-6 else w


def reference_steps(traffic: dict, vocab: int, seed: int,
                    n_steps: int) -> list:
    """The unique tasks of the first steps, as ``refs.*.train_steps``
    takes them: tokens / labels [k, T, S] and each task's row weight
    (G w)_i / (k T)."""
    code, tr = traffic["code"], traffic["trainer"]
    G = np.asarray(code["G"], dtype=np.float64)
    k = G.shape[0]
    T, S = tr["rows_per_slot"], tr["seq_len"]
    lat = latencies(traffic, seed)
    out = []
    for step in range(n_steps):
        mask = arrived(traffic, lat[step % lat.shape[0]])
        w = decode_weights(G, mask, tr["decoder"], code["s"])
        data = np.stack([task_tokens(traffic["data"]["seed"], step, i, T, S,
                                     vocab) for i in range(k)])
        out.append({"tokens": data[:, :, :-1].astype(np.int32),
                    "labels": data[:, :, 1:].astype(np.int32),
                    "coeff": G @ w / (k * T), "w": w})
    return out


FAULTS = ("half_batch", "exchange")


def plant(fault: str, steps: list, traffic: dict, chips: int) -> list:
    """The reference's steps with one fault of the timed path planted:

    half_batch  every other held task left out, the loss taken as the
                mean over the rest (their weights doubled);
    exchange    no gradient exchange between chips: what the first chip
                applies is the loss and gradient of its own workers
                (the first n / chips of them) alone.
    """
    G = np.asarray(traffic["code"]["G"], dtype=np.float64)
    k = G.shape[0]
    T = traffic["trainer"]["rows_per_slot"]
    out = []
    for st in steps:
        st = dict(st)
        if fault == "half_batch":
            held = np.flatnonzero(st["coeff"] != 0)
            keep = np.zeros(k, dtype=bool)
            keep[held[::2]] = True
            c = np.where(keep, st["coeff"], 0.0)
            st["coeff"] = c * (st["coeff"].sum() / max(c.sum(), 1e-30))
        elif fault == "exchange":
            lanes = -(-G.shape[1] // chips)
            st["coeff"] = G[:, :lanes] @ st["w"][:lanes] / (k * T)
        else:
            raise ValueError(f"fault {fault!r} not in {FAULTS}")
        out.append(st)
    return out
