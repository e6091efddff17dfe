"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --smoke \
        --code bgc --decoder onestep --steps 50 [--straggler deadline] \
        [--mesh debug --mesh-data 2 --mesh-model 2]

Selects any assigned architecture (``--arch``), builds the gradient code,
wires the straggler model and fault plan, and runs the CodedTrainer.
``--smoke`` selects a reduced same-family config that runs on a CPU
host; the full configs run on TPU (``chip_smoke.py`` trains minicpm-2b
at its published widths, cut in depth, on one v5e chip).
"""

from __future__ import annotations

import argparse
import dataclasses
import json

from repro.configs import get_config, list_archs
from repro.core import registry
from repro.models import build_model
from repro.optim import OptConfig
from repro.platform import backend_info, enable_compile_cache
from repro.runtime import FaultInjector, make_straggler_model
from repro.runtime.faults import FaultPlan
from repro.training import CodedTrainConfig, CodedTrainer

STRAGGLER_PRESETS = {
    "none": {},
    "iid": {"delta": 0.2},
    "fixed": {"delta": 0.25},
    "deadline": {"deadline": 1.5, "tail_scale": 0.3},
    "correlated": {"pod_size": 4, "p_pod": 0.1},
    "clustered": {"blocks": 4, "p_block": 0.15},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    # scheme choices come from the registry: registering a family in
    # core/registry.py is all it takes to reach this CLI
    ap.add_argument("--code", default="bgc", choices=list(registry.names()))
    ap.add_argument("--decoder", default="onestep",
                    choices=list(registry.DECODERS))
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--s", type=int, default=3)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--straggler", default="fixed",
                    choices=list(STRAGGLER_PRESETS))
    ap.add_argument("--fail-step", type=int, default=None,
                    help="inject a hard worker failure at this step")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--dist-mode", default="fused",
                    choices=["fused", "coded_allreduce"],
                    help="'coded_allreduce' runs the shard_map coded "
                         "aggregation over a 1-D worker mesh spanning all "
                         "local devices (docs/architecture.md §9)")
    ap.add_argument("--trace", default="none",
                    choices=["none", "pareto", "bimodal", "clustered"],
                    help="drive straggler masks from a latency trace "
                         "through --sync-policy instead of --straggler")
    ap.add_argument("--sync-policy", default="deadline",
                    choices=["sync", "deadline", "backup", "adaptive"])
    ap.add_argument("--adaptive", action="store_true",
                    help="close the loop: an AdaptiveCoder controller "
                         "(repro.control) observes the straggler process "
                         "and re-tunes s / decoder / deadline online "
                         "(docs/adaptive.md)")
    ap.add_argument("--error-budget", type=float, default=0.05,
                    help="mean decode err/k the adaptive controller "
                         "steers under (with --adaptive)")
    ap.add_argument("--mesh", default="none", choices=["none", "debug"],
                    help="'debug' builds a small host mesh (needs a "
                         "forced host-device world — call "
                         "repro.platform.host_devices(n) before jax, or "
                         "export REPRO_HOST_DEVICES=n)")
    ap.add_argument("--mesh-data", type=int, default=2)
    ap.add_argument("--mesh-model", type=int, default=2)
    ap.add_argument("--history-out", default=None)
    args = ap.parse_args(argv)

    enable_compile_cache()
    info = backend_info()
    print(f"[train] device: platform={info.platform} "
          f"kind={info.device_kind!r} count={info.device_count}")
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.moe is not None:
        # coded training computes expert layers dropless (CodedTrainer)
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="dropless"))
    model = build_model(cfg)
    print(f"[train] {cfg.name}: {model.param_count() / 1e6:.1f}M params, "
          f"code={args.code} s={args.s} decoder={args.decoder} "
          f"workers={args.workers}")

    mesh = None
    if args.mesh == "debug":
        from .mesh import make_debug_mesh
        mesh = make_debug_mesh(args.mesh_data, args.mesh_model)
        print(f"[train] mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    straggler = (make_straggler_model(args.straggler,
                                      **STRAGGLER_PRESETS[args.straggler])
                 if args.straggler != "none" else None)
    trace = None
    if args.trace != "none":
        from repro.sim.traces import make_trace
        trace = make_trace(args.trace, steps=args.steps, n=args.workers,
                           seed=args.seed)
        straggler = None    # masks come from the trace + sync policy
        print(f"[train] trace: {args.trace} x {args.steps} steps, "
              f"policy={args.sync_policy}")
    faults = None
    if args.fail_step is not None:
        faults = FaultInjector([FaultPlan(step=args.fail_step,
                                          workers=(args.workers - 1,))])

    controller = None
    if args.adaptive:
        from repro.control import AdaptiveCoder, ControlConfig
        controller = AdaptiveCoder(
            args.code, args.workers,
            ControlConfig(error_budget=args.error_budget),
            s=args.s, decoder=args.decoder)
        print(f"[train] adaptive controller: error budget "
              f"{args.error_budget}")

    tcfg = CodedTrainConfig(
        code=args.code, n_workers=args.workers, s=args.s,
        decoder=args.decoder, seq_len=args.seq_len, steps=args.steps,
        seed=args.seed,
        opt=OptConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps),
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        log_every=max(args.steps // 10, 1), dist_mode=args.dist_mode)
    trainer = CodedTrainer(model, tcfg, straggler_model=straggler,
                           fault_injector=faults, mesh=mesh,
                           trace=trace,
                           sync_policy=args.sync_policy if trace else None,
                           controller=controller)
    if trainer.allreduce is not None:
        print(f"[train] coded_allreduce: {trainer.allreduce.n_devices} "
              f"device(s) x {trainer.allreduce.partition.lanes} lane(s)")
    out = trainer.run()

    for h in out["history"]:
        print(f"  step {h['step']:>5} ce={h['mean_ce']:.4f} "
              f"stragglers={h['stragglers']} "
              f"decode_err/k={h['decode_err']:.4f} workers={h['n_workers']}"
              + (f" s={h['s']} dec={h['decoder']}" if args.adaptive else ""))
    if controller is not None and controller.policy.actions:
        print("[train] controller actions:")
        for at_step, act in controller.policy.actions:
            print(f"  step {at_step:>5} {act.kind} -> {act.value}  "
                  f"({act.reason})")
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(out["history"], f, indent=1)
    first, last = out["history"][0]["mean_ce"], out["history"][-1]["mean_ce"]
    print(f"[train] ce {first:.4f} -> {last:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
