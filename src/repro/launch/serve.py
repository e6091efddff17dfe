"""Serving launcher CLI (batched prefill + continuous-batching decode).

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --smoke \
        --requests 8 --max-new 12

``--sim`` skips the model entirely and replays a latency trace through
the multi-replica hedged-serving simulator instead (E12 interactive):

    PYTHONPATH=src python -m repro.launch.serve --sim --trace bimodal \
        --replicas 8 --requests 1000000 --hedge-quantile 0.85
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def _run_sim(args) -> int:
    from repro.serving import HedgePolicy, simulate_serving
    from repro.sim.traces import make_trace

    trace = make_trace(args.trace, steps=args.trace_steps, n=args.replicas,
                       seed=args.seed)
    policy = None
    if args.hedge_quantile > 0:
        policy = HedgePolicy(quantile=args.hedge_quantile)
    t0 = time.time()
    res = simulate_serving(trace, args.requests, policy=policy,
                           router_policy=args.router, seed=args.seed)
    dt = time.time() - t0
    mode = (f"hedge@q{args.hedge_quantile}" if policy else "unhedged")
    print(f"[serve --sim] {args.trace} x{args.replicas} replicas, "
          f"{args.requests} requests ({mode}, {args.router} routing): "
          f"{dt:.1f}s")
    for q, v in sorted(res.quantiles.items()):
        print(f"  p{100 * q:<5g} {v:.3f}")
    print(f"  mean_compute {res.mean_compute:.3f}  "
          f"hedge_rate {res.hedge_rate:.3f}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sim", action="store_true",
                    help="replay a trace through the multi-replica "
                         "simulator (no model)")
    ap.add_argument("--trace", default="bimodal",
                    help="trace source for --sim (see sim.traces)")
    ap.add_argument("--trace-steps", type=int, default=32_768)
    ap.add_argument("--replicas", type=int, default=8)
    ap.add_argument("--hedge-quantile", type=float, default=0.85,
                    help="0 disables hedging")
    ap.add_argument("--router", default="uniform",
                    choices=("uniform", "p2c"))
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.sim:
        return _run_sim(args)

    import jax

    from repro.configs import get_config, list_archs
    from repro.models import build_model
    from repro.platform import backend_info, enable_compile_cache
    from repro.serving import Request, ServingEngine

    if args.arch is None or args.arch not in list_archs():
        ap.error(f"--arch is required without --sim "
                 f"(choices: {', '.join(list_archs())})")

    enable_compile_cache()
    info = backend_info()
    print(f"[serve] device: platform={info.platform} "
          f"kind={info.device_kind!r} count={info.device_count}")
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    print(f"[serve] {cfg.name}: {model.param_count() / 1e6:.1f}M params")

    engine = ServingEngine(model, params, batch_slots=args.slots,
                           cache_len=args.prompt_len + args.max_new + 8)
    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, cfg.vocab,
                                        args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    results = engine.serve_queue(reqs)
    dt = time.time() - t0
    tok = sum(len(v) for v in results.values())
    print(f"[serve] {len(results)} requests, {tok} tokens, {dt:.1f}s "
          f"({tok / dt:.1f} tok/s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
