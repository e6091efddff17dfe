"""The harness's comparison on a run whose timed path is broken
underneath: each fault a training cell can have makes ``correct`` come
out false, and the sound path makes it true.  CPU, small widths, the
cells' own limits; the look for a chip is skipped (platform="cpu")."""

import io
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from tiny import tiny_spec

CELL = "minicpm-2b.coded-frc8"


def run(spec, seed=2**31 + 77) -> dict:
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(spec, seed, 0.3, False, t_start=time.perf_counter(),
                          platform="cpu", out=out, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct():
    res = run(tiny_spec(CELL))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["checks"])[-1] == "code_graph"
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from repro.training import CodedTrainer

    make = CodedTrainer._make_step_fn

    def broken(self):
        step = make(self)

        def same(params, opt_state, batch):
            # the program's step donates its inputs: hand it copies
            _, _, metrics = step(*jax.tree_util.tree_map(
                jnp.copy, (params, opt_state)), batch)
            return params, opt_state, metrics
        return same

    monkeypatch.setattr(CodedTrainer, "_make_step_fn", broken)
    res = run(tiny_spec(CELL))
    assert not res["correct"]
    assert res["checks"]["update"]["value"] > res["checks"]["update"]["limit"]


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from repro.data import CodedDataPipeline

    batch_for_step = CodedDataPipeline.batch_for_step

    def half(self, step, decode_w):
        b = batch_for_step(self, step, decode_w)
        w = np.asarray(b["loss_weight"], dtype=np.float64)
        keep = np.zeros_like(w)
        keep[::4] = keep[1::4] = 1.0      # half of the workers' rows
        b["loss_weight"] = w * keep * (w.sum() / max((w * keep).sum(), 1e-30))
        return b

    monkeypatch.setattr(CodedDataPipeline, "batch_for_step", half)
    res = run(tiny_spec(CELL))
    assert not res["correct"], res["checks"]


def test_a_code_graph_other_than_the_stated_one_is_not_correct():
    spec = tiny_spec(CELL)
    G = np.asarray(spec["traffic"]["code"]["G"])
    spec["traffic"]["code"]["G"] = G[::-1].tolist()
    res = run(spec)
    assert not res["correct"]
    assert res["checks"]["code_graph"]["value"] == 1


FOUR_CHIPS = r"""
import io, json, sys, time
sys.path.insert(0, sys.argv[1])
sys.path.insert(0, sys.argv[1] + "/tests/bench")
import jax
from bench import harness
from tiny import tiny_spec
out = {}
for name in ("sound", "exchange"):
    if name == "exchange":
        jax.lax.psum = lambda x, *a, **k: x   # no exchange between chips
    buf, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(tiny_spec("minicpm-2b.allreduce-4chip", chips=4),
                          2**33 + 5, 0.3, False, t_start=time.perf_counter(),
                          platform="cpu", out=buf, err=err)
    assert rc == 0, err.getvalue()[-2000:]
    out[name] = json.loads(buf.getvalue().strip().splitlines()[-1])
print("RESULT " + json.dumps(out))
"""


def test_exchange_between_chips_left_out_is_not_correct():
    root = Path(harness.ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(root / "src"))
    proc = subprocess.run([sys.executable, "-c", FOUR_CHIPS, str(root)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    res = json.loads(line[-1][len("RESULT "):])
    assert res["sound"]["correct"], res["sound"]["checks"]
    assert res["sound"]["device"]["count"] == 4
    assert not res["exchange"]["correct"], res["exchange"]["checks"]


@pytest.mark.parametrize("platform", ["tpu"])
def test_a_run_without_the_chip_prints_no_result(platform):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(tiny_spec(CELL), 1, 0.1, False,
                          t_start=time.perf_counter(), platform=platform,
                          out=out, err=err)
    assert rc != 0 and out.getvalue() == ""
