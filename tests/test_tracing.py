"""The program's trace marks (repro.tracing): the host spans of the coded
training loop, read back from a profiler trace, and the named scopes of
the model step, read from the compiled program's op names."""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as CFG
from repro import tracing
from repro.models import build_model
from repro.optim import OptConfig
from repro.runtime import FaultInjector, FaultPlan
from repro.training import CodedTrainConfig, CodedTrainer

pytestmark = pytest.mark.slow  # jit + a profiled training loop

PER_STEP = (tracing.DECODE, tracing.BATCH, tracing.H2D, tracing.DISPATCH)


def make_trainer(model_cfg=None, faults=None, **kw):
    cfg = model_cfg or CFG.get_config("minicpm-2b", smoke=True)
    defaults = dict(code="frc", n_workers=8, s=2, decoder="onestep",
                    rows_per_slot=1, seq_len=16, steps=8, seed=0,
                    opt=OptConfig(lr=1e-3, warmup_steps=2, total_steps=50),
                    log_every=2)
    defaults.update(kw)
    return CodedTrainer(build_model(cfg), CodedTrainConfig(**defaults),
                        fault_injector=faults)


def traced_spans(trainer, tmp_path, start_step, steps, state=None):
    """Run ``steps`` steps under the profiler; returns the state and the
    ``repro.`` host events as (thread, name, start_ns, end_ns)."""
    with jax.profiler.trace(str(tmp_path)):
        state = trainer.run(state, start_step=start_step,
                            steps=steps)["state"]
    files = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(max(files,
                                                key=os.path.getmtime))
    spans = [(f"{p.name}/{line.name}", e.name, e.start_ns, e.end_ns)
             for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events
             if e.name.startswith("repro.")]
    return state, spans


def test_loop_spans_once_a_step_and_never_nested(tmp_path):
    tr = make_trainer()
    state = tr.run(start_step=0, steps=1)["state"]     # compile first
    steps = 4                                           # steps 1..4
    _, spans = traced_spans(tr, tmp_path, 1, steps, state)
    names = [n for _, n, _, _ in spans]
    for name in PER_STEP:
        assert names.count(name) == steps, name
    # steps 2 and 4 are log steps (log_every=2); step 4 is also the last
    assert names.count(tracing.LOG) == 2
    assert set(names) <= set(tracing.HOST_SPANS)
    assert tracing.RECODE not in names and tracing.CKPT not in names
    # leaves: no span of the program overlaps another on its thread
    by_thread = {}
    for thread, name, s, e in spans:
        by_thread.setdefault(thread, []).append((s, e, name))
    for events in by_thread.values():
        events.sort()
        for (s0, e0, n0), (s1, e1, n1) in zip(events, events[1:]):
            assert e0 <= s1, f"{n0} overlaps {n1}"
    # a step's phases run in order: decode, batch, transfer, dispatch
    order = [n for n in names if n in PER_STEP]
    assert order == list(PER_STEP) * steps


def test_recode_and_checkpoint_spans_fire_only_when_they_happen(tmp_path):
    faults = FaultInjector([FaultPlan(step=2, workers=[7])])
    tr = make_trainer(faults=faults, code="bgc", ckpt_dir=str(tmp_path / "ck"),
                      ckpt_every=3, steps=4)
    _, spans = traced_spans(tr, tmp_path / "trace", 0, 4)
    names = [n for _, n, _, _ in spans]
    assert names.count(tracing.RECODE) == 1        # the fault at step 2
    assert names.count(tracing.CKPT) == 1          # after step 3
    assert names.count(tracing.DISPATCH) == 4


def test_pipelined_decode_is_spanned_twice_a_step(tmp_path):
    """staleness=1: the weight choice before the batch, and today's
    decode issued after the dispatch, each a span of its own."""
    tr = make_trainer(staleness=1)
    state = tr.run(start_step=0, steps=1)["state"]
    _, spans = traced_spans(tr, tmp_path, 1, 3, state)
    names = [n for _, n, _, _ in spans]
    assert names.count(tracing.DECODE) == 6
    assert names.count(tracing.DISPATCH) == 3


OP = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? (dot|convolution)\(.*"
                r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def scopes_in(op_name):
    """The scopes that are whole components of an op_name, transform
    wrappers such as jvp(..) and transpose(..) read through.  A scope
    recomputed under jax.checkpoint appears twice in the path
    ('transpose(jvp(head_ce))/jvp(head_ce)/checkpoint/..'): counted once."""
    found = set()
    for part in op_name.split("/"):
        while (m := re.match(r"^(?:jvp|transpose|vmap)\((.*)\)$", part)):
            part = m.group(1)
        if part in tracing.SCOPES:
            found.add(part)
    return found


@pytest.mark.parametrize("loss_chunk,remat", [(0, "none"), (8, "dots")])
def test_every_matmul_of_the_step_lies_in_one_scope(loss_chunk, remat):
    cfg = dataclasses.replace(CFG.get_config("minicpm-2b", smoke=True),
                              loss_chunk=loss_chunk, remat=remat)
    tr = make_trainer(cfg)
    state = tr.init_state()
    w = tr.decode_weights_for(np.ones(tr.assignment.n, dtype=bool))
    batch = {k: jnp.asarray(v)
             for k, v in tr.pipeline.batch_for_step(0, w).items()}
    hlo = tr._step_fn.lower(state["params"], state["opt"],
                            batch).compile().as_text()
    ops = [m.groups() for m in map(OP.match, hlo.splitlines()) if m]
    assert len(ops) >= 10
    for opcode, op_name in ops:
        assert len(scopes_in(op_name)) == 1, (opcode, op_name)
    seen = set().union(*(scopes_in(n) for _, n in ops))
    # the optimizer runs no matmul; its scope holds the update itself
    assert seen == {tracing.ATTENTION, tracing.MLP, tracing.HEAD_CE}
    assert f"/{tracing.OPTIMIZER}/" in hlo


def test_log_record_is_one_device_read(monkeypatch):
    """A log step reads its scalars back in one device_get, and history
    keeps them as Python floats of the step's float32 metrics."""
    tr = make_trainer(steps=3, log_every=1)
    state = tr.run(start_step=0, steps=1)["state"]
    reads = []
    device_get = jax.device_get

    def counting(x):
        reads.append(x)
        return device_get(x)

    monkeypatch.setattr(jax, "device_get", counting)
    out = tr.run(state, start_step=1, steps=2)
    assert len(reads) == 2 and all(len(r) == 3 for r in reads)
    assert len(out["history"]) == 3
    for rec in out["history"]:
        for key in ("loss", "mean_ce", "grad_norm"):
            assert type(rec[key]) is float
            assert rec[key] == float(np.float32(rec[key]))
