"""One run of one cell: set-up, a timed window through the program's own
training entry point, and the comparison that decides ``correct``.

Everything that belongs to a configuration, a traffic mix or a metric
is found by name:

    BENCHMARK.json                 cells, metrics, configuration files
    bench/configs/<config>.json    the model as it is run
    bench/refs/<reference>.py      its plain reference (named in the file)
    bench/traffic/<traffic>.json   the job: code, trace, batch, optimizer
    bench/limits/<cell>.json       the limits of the compared numbers
    bench/metrics/<metric>.py      one per-layer metric reader each

The window calls ``CodedTrainer.run(state, start_step=i, steps=chunk)``
until ``--seconds`` have passed; a chunk ends when its last step's log
has read the step's metrics back, so the program syncs once a chunk.
"""

from __future__ import annotations

import functools
import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
CHECK_STEPS = 3     # steps the reference follows
ALIGN = 1           # window chunks start one past a multiple of log_every


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic, limits and metrics."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return {
        "cell": cell,
        "config": json.loads((root / cfg_entry["file"]).read_text()),
        "traffic": json.loads((root / "bench" / "traffic"
                               / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads(
            (root / "bench" / "limits" / f"{name}.json").read_text()),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling, with the count
    of backend compiles and persistent-cache hits, since the last
    reset()."""

    _SPANS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def reset(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

    def _span(self, event, secs, **_):
        if event in self._SPANS:
            self.seconds += secs
            self.compiles += event == self._SPANS[-1]

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"


def peak_bytes(devices) -> int:
    """The fullest chip's peak, as the device reports it."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def enable_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program cached however fast it compiled."""
    import jax
    path = str(root / ".jax_cache" / "bench")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _gap(prog: np.ndarray, ref: np.ndarray, floor: float) -> float:
    """Worst |prog - ref| over max(|ref|, floor), elementwise."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape or not np.all(np.isfinite(prog)):
        return math.inf
    den = np.maximum(np.abs(ref), floor)
    return float(np.max(np.abs(prog - ref) / den))


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``:

    loss    worst relative gap of the first steps' logged losses;
    grad    worst leaf's gap of the first gradient's norm as the
            optimizer got it, over the larger of the reference leaf's
            norm and the median leaf's;
    update  the same for the norm of the parameters' change over the
            steps, on the leaves the reference's gradient moves (a
            first-gradient norm of at least a thousandth of the median
            leaf's; below that a leaf moves by round-off alone).
    """
    g_ref = np.asarray(ref["first_grad"])
    g_med = float(np.median(g_ref))
    moved = g_ref >= 1e-3 * g_med
    d_ref = np.asarray(ref["update"])[moved]
    return {
        "loss": _gap(prog["loss"], ref["loss"], 0.0),
        "grad": _gap(prog["first_grad"], g_ref, g_med),
        "update": _gap(np.asarray(prog["update"])[moved], d_ref,
                       float(np.median(d_ref))),
    }


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Cell:
    """The program under test, built for one cell, and the benchmark's
    own weights, norms and reference around it."""

    def __init__(self, spec: dict, root: Path = ROOT):
        import jax
        import jax.numpy as jnp

        sys.path.insert(0, str(root / "src"))
        self.spec = spec
        self.mcfg = spec["config"]["model"]
        self.traffic = spec["traffic"]
        ref_name = spec["config"]["reference"]
        self.ref = load_module(BENCH / "refs" / f"{ref_name}.py",
                               f"bench_ref_{ref_name}")
        mcfg, ref = self.mcfg, self.ref

        def weights(lo, hi):
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.PRNGKey(0x5EED), lo), hi)
            return ref.init_params(mcfg, key)

        def change(params, lo, hi):
            return leaf_norms_dev(jax.tree_util.tree_map(
                jnp.subtract, params, weights(lo, hi)))

        self.weights = weights
        self.change = jax.jit(change)

    def build(self, seed: int):
        """A CodedTrainer for the cell, its straggler trace drawn from the
        seed; the state made from the seed on the trainer's devices."""
        import jax
        from repro.models import ArchConfig, build_model
        from repro.optim import OptConfig, init_opt_state
        from repro.sim.cluster import make_policy
        from repro.sim.traces import LatencyTrace
        from repro.training import CodedTrainConfig, CodedTrainer

        from . import jobs

        code, tr, opt = (self.traffic[k] for k in ("code", "trainer", "opt"))
        pol = self.traffic["sync_policy"]
        tcfg = CodedTrainConfig(
            code=code["family"], n_workers=code["n"], s=code["s"],
            decoder=tr["decoder"], rows_per_slot=tr["rows_per_slot"],
            seq_len=tr["seq_len"], seed=code["seed"],
            log_every=tr["log_every"], dist_mode=tr["dist_mode"],
            opt=OptConfig(**{k: v for k, v in opt.items() if k != "decay"}))
        trainer = CodedTrainer(
            build_model(ArchConfig(**self.mcfg)), tcfg,
            trace=LatencyTrace(jobs.latencies(self.traffic, seed),
                               source="pareto"),
            sync_policy=make_policy(pol["kind"], deadline=pol["deadline"]))
        out_sh = None
        if trainer.allreduce is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            out_sh = NamedSharding(trainer.allreduce.mesh, PartitionSpec())
        with _annotate("bench.setup.weights"):
            params = jax.jit(self.weights, out_shardings=out_sh)(
                *seed_words(seed))
            state = {"params": params, "opt": jax.jit(
                init_opt_state, out_shardings=out_sh)(params)}
        return trainer, state

    def first_steps(self, trainer, state, seed: int):
        """Drive the trainer through its first steps by the window's own
        call; returns the state and the numbers the reference checks."""
        import jax

        prog = {"loss": []}
        for step in range(CHECK_STEPS):
            state = trainer.run(state, start_step=step, steps=1)["state"]
            prog["loss"].append(trainer.history[-1]["loss"])
            if step == 0:
                prog["first_grad"] = leaf_norms(state["opt"]["mu"]) / (
                    1.0 - self.traffic["opt"]["b1"])
        prog["update"] = np.asarray(jax.device_get(self.change(
            state["params"], *seed_words(seed))), dtype=np.float64)
        prog["loss"] = np.asarray(prog["loss"], dtype=np.float64)
        return state, prog

    def reference(self, seed: int, precision: str = "fp32",
                  fault: Optional[str] = None) -> dict:
        """The reference's loss, first clipped gradient and change over
        the first steps, from the seed alone.  ``fault`` plants one of
        ``jobs.FAULTS`` in the reference put in the program's place."""
        import jax

        from . import jobs

        steps = jobs.reference_steps(self.traffic, self.mcfg["vocab"], seed,
                                     CHECK_STEPS)
        if fault is not None:
            steps = jobs.plant(fault, steps, self.traffic,
                               self.spec["cell"]["chips"])
        lo, hi = seed_words(seed)
        r = self.ref.train_steps(self.mcfg, self.traffic["opt"],
                                 jax.jit(self.weights)(lo, hi), steps,
                                 precision)
        params = r.pop("params")
        r["update"] = np.asarray(jax.device_get(
            self.change(params, lo, hi)), dtype=np.float64)
        return r

    def tokens_per_step(self, trainer) -> int:
        """Unique tokens a step trains: tasks held by at least one worker,
        times rows per slot, times positions."""
        ids = trainer.assignment.task_ids
        tr = self.traffic["trainer"]
        return int(np.unique(ids[ids >= 0]).size) * tr["rows_per_slot"] \
            * tr["seq_len"]


def leaf_norms_dev(tree):
    """Per-leaf L2 norms, on the device (traceable)."""
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@functools.lru_cache(maxsize=None)
def _leaf_norms_jit():
    import jax
    return jax.jit(leaf_norms_dev)


def leaf_norms(tree) -> np.ndarray:
    """Per-leaf L2 norms, float64 on the host, in flatten order."""
    import jax
    return np.asarray(jax.device_get(_leaf_norms_jit()(tree)),
                      dtype=np.float64)


def check_devices(cell: dict, platform: str, err) -> Optional[list]:
    import jax
    devices = jax.devices()
    if devices[0].platform != platform:
        print(f"bench: JAX found no {platform} device (platform "
              f"{devices[0].platform!r})", file=err)
        return None
    if len(devices) < cell["chips"]:
        print(f"bench: the cell asks for {cell['chips']} chips, JAX sees "
              f"{len(devices)}", file=err)
        return None
    return devices


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, platform: str = "tpu", root: Path = ROOT,
             out=sys.stdout, err=sys.stderr) -> int:
    """Run the cell; print the result line.  Returns the exit code."""
    import jax

    from . import flops as FL

    cell = spec["cell"]
    devices = check_devices(cell, platform, err)
    if devices is None:
        return 2
    dev = devices[0]
    if platform == "tpu":
        enable_cache(root)
    meter = CompileMeter()
    bc = Cell(spec, root)
    chunk = spec["traffic"]["trainer"]["log_every"]

    with _annotate("bench.setup.build"):
        trainer, state = bc.build(seed)
    G_ok = bool(np.array_equal(trainer.code.G,
                               np.asarray(spec["traffic"]["code"]["G"])))
    tokens_per_step = bc.tokens_per_step(trainer)
    with _annotate("bench.setup.first_steps"):
        state, prog = bc.first_steps(trainer, state, seed)
    with _annotate("bench.setup.warmup"):
        nxt = -(-(CHECK_STEPS - ALIGN) // chunk) * chunk + ALIGN
        state = trainer.run(state, start_step=CHECK_STEPS,
                            steps=nxt - CHECK_STEPS)["state"]
    setup_compile_s = meter.seconds
    setup_s = time.perf_counter() - t_start

    # ----------------------------- window -----------------------------
    meter.reset()
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir, profiler_options=_profile_options())
    step, done, bad = nxt, 0, 0
    t0 = time.perf_counter()
    while True:
        with _annotate("bench.chunk"):
            state = trainer.run(state, start_step=step, steps=chunk)["state"]
        rec = trainer.history[-1]
        bad += chunk * (not (math.isfinite(rec["loss"])
                             and math.isfinite(rec["grad_norm"])))
        step += chunk
        done += chunk
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    window_compiles = meter.compiles
    mem_peak = peak_bytes(devices[:cell["chips"]])

    # ------------------------- the reference --------------------------
    del state, trainer
    gc.collect()
    with jax.default_device(dev):
        r = bc.reference(seed)
    gaps = compare(prog, r)
    limits = spec["limits"]
    checks = {name: {"value": gaps[name], "limit": limits[name]}
              for name in ("loss", "grad", "update")}
    checks["code_graph"] = {"value": 0 if G_ok else 1, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    # what the per-layer metric readers see
    run = SimpleNamespace(
        cell=cell, config=bc.mcfg, traffic=bc.traffic, chips=cell["chips"],
        device_kind=dev.device_kind, setup_s=setup_s,
        setup_compile_s=setup_compile_s, window_s=window_s, steps=done,
        tokens_per_step=tokens_per_step,
        tokens_per_s=done * tokens_per_step / window_s, flops=FL, trace=None)
    result = {"correct": correct, "attempted": done, "failed": bad}
    if trace:
        import shutil

        from . import xplane
        run.trace = xplane.reduce_dir(tdir, n_devices=cell["chips"])
        shutil.rmtree(tdir, ignore_errors=True)     # kept if unreadable
        metrics = {}
        for m in spec["per_layer"]:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["metrics"] = metrics
        result["breakdown"] = run.trace["breakdown"]
    else:
        e2e = {"setup_s": setup_s, "train_tokens_per_s": run.tokens_per_s}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in spec["end_to_end"]}
    result["device"] = device
    result["checks"] = checks

    print(f"bench: {cell['name']} seed={seed} steps={done} "
          f"window_s={window_s!r} setup_s={setup_s!r} "
          f"setup_compile_s={setup_compile_s!r} "
          f"window_compiles={window_compiles} "
          f"tokens_per_step={tokens_per_step}", file=err)
    print(f"bench: prog loss={prog['loss'].tolist()} ref loss="
          f"{r['loss'].tolist()}", file=err)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


def seed_words(seed: int):
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def main(argv: Optional[list] = None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("bench: --seed must be a whole number >= 0", file=sys.stderr)
        return 2
    spec = load_cell(args.workload)
    return run_cell(spec, args.seed, args.seconds, bool(args.trace),
                    t_start=t_start)
