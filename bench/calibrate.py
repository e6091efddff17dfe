"""Readings that the limits of a cell's compared numbers are set from.
Run on the chip at the cell's own size; the benchmark's runs never run
this.

    python3 bench/calibrate.py --workload <cell> --seeds <a,b,...> \
        [--control-seeds 3] --out <dir>

In one process, for each seed: the program, built and driven through its
first steps exactly as a benchmark run does, against the float32
reference (the lower readings).  For the first ``--control-seeds``
seeds also: the control, the reference computed with float8 matmuls in
the program's place, and each planted fault that the cell can have, all
against the same float32 reference (the upper readings).  A step that
returns its state unchanged needs no run: its first gradient and its
change read 0, so both numbers read 1.

Prints one JSON line per reading and writes them all to
``<out>/<cell>.json``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True,
                    help="directory for <cell>.json with every reading")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    import jax

    spec = harness.load_cell(args.workload)
    devices = harness.check_devices(spec["cell"], "tpu", sys.stderr)
    if devices is None:
        return 2
    harness.enable_cache(harness.ROOT)
    cell = harness.Cell(spec)
    faults = ["half_batch"] + (["exchange"] if spec["cell"]["chips"] > 1
                               else [])
    rows = []

    def emit(kind, seed, gaps, **extra):
        row = {"cell": args.workload, "kind": kind, "seed": seed, **gaps,
               **extra, "t": time.perf_counter() - T_START}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        trainer, state = cell.build(seed)
        state, prog = cell.first_steps(trainer, state, seed)
        extra = {"build_and_steps_s": time.perf_counter() - t0,
                 "peak_bytes_in_use": harness.peak_bytes(
                     devices[:spec["cell"]["chips"]])}
        del state, trainer
        gc.collect()
        with jax.default_device(devices[0]):
            t0 = time.perf_counter()
            ref = cell.reference(seed)
            extra["reference_s"] = time.perf_counter() - t0
            emit("program", seed, harness.compare(prog, ref), **extra,
                 loss_prog=prog["loss"].tolist(),
                 loss_ref=ref["loss"].tolist())
            if i < args.control_seeds:
                ctl = cell.reference(seed, precision="fp8")
                emit("control_fp8", seed, harness.compare(ctl, ref))
                for fault in faults:
                    got = cell.reference(seed, fault=fault)
                    emit(f"fault_{fault}", seed, harness.compare(got, ref))
        gc.collect()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
