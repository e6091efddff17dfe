"""Benchmark harness entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--trials N]

Runs, in order (E-numbers from docs/architecture.md §4):
    E1-E3  fig_errors        Figs 2-4: err1/err vs delta per scheme
    E4     fig5_algorithmic  Fig 5: ||u_t||^2/k curves
    E5     theory_check      Thms 5/6/7/8/21 closed forms vs Monte Carlo
    E6     adversary_bench   Thm 10/11: adversaries + NP-hardness reduction
    E7     e2e_convergence   coded LM training vs baselines + wall-clock
    E8     decoding_cost     decoder microbenchmarks vs k
    E9     roofline_report   roofline table from the dry-run artifacts
    E10    mc_throughput     looped vs batched Monte-Carlo decode
    E11    wallclock_frontier  ClusterSim runtime-vs-accuracy frontier
    E12    serving_tail      hedged-serving p99/p999 vs compute overhead
    E13    elastic_churn     time-to-target through membership churn

Artifacts land in artifacts/bench/ (+ artifacts/roofline.{json,md});
each module prints PASS/MISMATCH against the paper's claims.
"""

from __future__ import annotations

import argparse
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced trial counts (CI mode)")
    ap.add_argument("--trials", type=int, default=None,
                    help="Monte-Carlo trials (default 1000; paper used 5000)")
    ap.add_argument("--only", default=None,
                    help="comma list of module names to run")
    args = ap.parse_args(argv)

    from repro.platform import enable_compile_cache

    enable_compile_cache()
    trials = args.trials or (200 if args.quick else 1000)
    steps = 16 if args.quick else 40

    from . import adversary_bench, decoding_cost, e2e_convergence, \
        fig5_algorithmic, fig_errors, theory_check
    from . import elastic_churn, mc_throughput, roofline_report, \
        serving_tail, wallclock_frontier

    jobs = [
        ("fig_errors", lambda: fig_errors.main(["--trials", str(trials)])),
        ("fig5_algorithmic",
         lambda: fig5_algorithmic.main(["--trials", str(trials)])),
        ("theory_check",
         lambda: theory_check.main(["--trials", str(max(trials * 2, 400))])),
        ("adversary_bench", lambda: adversary_bench.main([])),
        ("e2e_convergence",
         lambda: e2e_convergence.main(["--steps", str(steps)])),
        ("decoding_cost", lambda: decoding_cost.main([])),
        ("mc_throughput",
         lambda: mc_throughput.main(["--trials", str(trials)])),
        ("wallclock_frontier",
         lambda: wallclock_frontier.main(
             ["--steps", str(max(trials // 2, 100))])),
        # E12 is vectorized numpy replay: the >= 1M-request gate stays
        # full-scale even under --quick (seconds, no device execution)
        ("serving_tail", lambda: serving_tail.main([])),
        # E13's storm is analytic (seconds); --quick skips only the
        # jitted trainer-recovery section, which the slow test lane
        # already covers
        ("elastic_churn", lambda: elastic_churn.main(
            ["--skip-trainer"] if args.quick else [])),
        ("roofline_report", lambda: roofline_report.main([])),
    ]
    if args.only:
        keep = {s.strip() for s in args.only.split(",")}
        jobs = [j for j in jobs if j[0] in keep]

    failures = []
    for name, fn in jobs:
        print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}", flush=True)
        t0 = time.time()
        try:
            rc = fn()
        except SystemExit as e:  # argparse in submodules
            rc = int(e.code or 0)
        except Exception as e:
            import traceback
            traceback.print_exc()
            rc = 2
        print(f"-- {name}: rc={rc} ({time.time() - t0:.1f}s)")
        if rc:
            failures.append(name)

    print(f"\n{'=' * 72}")
    if failures:
        print(f"BENCHMARKS WITH MISMATCHES/ERRORS: {failures}")
    else:
        print("ALL BENCHMARKS PASS")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
