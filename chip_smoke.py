"""Drive the main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: train, decode, serve
    python chip_smoke.py --chips 4    # four chips: coded all-reduce vs fused

One process, no child processes; ``src/`` is put on ``sys.path`` here,
so no PYTHONPATH is needed.  The model is minicpm-2b at its published
widths (d_model 2304, 36 heads of 64, d_ff 5760, vocab 122753) with
random weights from a seed; only its depth is cut, to the deepest that
leaves a quarter of one v5e chip's 16 GiB free in the compiled training
step.

Phases on one chip, each printing its device, its compile seconds
(trace + lower + compile, with persistent-cache hits) and the device's
``peak_bytes_in_use``, then its check:

  train   CodedTrainer, fused mode, frc n=8 s=2, one-step decoder, masks
          from a seeded Pareto latency trace under a deadline policy.
          Every mean_ce finite, the first near ln(vocab), stragglers
          dropped, decode_err logged.
  decode  DecodeEngine(backend="pallas") decode_batch for onestep (the
          ELL route and the dense route), optimal (masked Gram) and
          algorithmic, on a bgc code with n=256 s=8 and B=1000 seeded
          masks, against backend="numpy" within fp32 tolerances.
  serve   ServingEngine.serve_queue answers 6 requests on 4 slots with
          the trained weights; every request gets max_new_tokens, and
          each served token is a top logit of the plain full forward.

``--chips 4`` runs only the training phase twice: coded_allreduce over a
worker mesh of the four chips (2 worker lanes each), then fused mode on
the first chip, and compares their mean_ce curves.

The script exits nonzero, without its last line, when JAX finds no TPU
or a phase fails.  Its last line on stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "minicpm-2b"
LAYERS = 4          # compiled step 11.5 GB of the chip's 16.9 GB; 5 -> 12.9
SEQ_LEN = 256
LOSS_CHUNK = 128    # head + CE in sequence chunks: no [16, 256, V] logits
STEPS = 5
SEED = 0

DECODE_N, DECODE_S, DECODE_B, DECODE_DELTA = 256, 8, 1000, 0.2
DECODE_BACKEND = "pallas"
# fp32 kernels (Precision.HIGHEST contractions) against the float64 numpy
# decoder: relative tolerance of the errors, and of the weights relative
# to their largest magnitude
DECODE_ERR_RTOL, DECODE_W_TOL = 1e-5, 1e-6

SERVE_REQUESTS, SERVE_SLOTS, SERVE_PROMPT, SERVE_NEW = 6, 4, 64, 8
# a served token must be within this share of the reference logits'
# spread (top minus mean) of the reference's top logit
SERVE_GAP = 0.1

# coded_allreduce vs fused mean_ce, per step: both compute in bf16 and
# only their reduction order and per-device batch shapes differ, but
# Adam's first updates are sign(g), so near-zero gradients whose sign
# the rounding flips still move every later step a little
DIST_RTOL = 5e-3


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling, with the count
    of backend compiles and persistent-cache hits, since the last take()."""

    _SPANS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, secs, **_):
        if event in self._SPANS:
            self.seconds += secs
            self.compiles += event == self._SPANS[-1]

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def take(self) -> str:
        out = (f"compile_s={self.seconds:.2f} compiles={self.compiles} "
               f"cache_hits={self.cache_hits}")
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        return out


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def peak_bytes() -> str:
    """Per-device peaks; on v5e a program's temporaries count as reserved
    bytes, not as bytes in use."""
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return " ".join(
        f"{key}=" + ",".join(str(st.get(key, -1)) for st in stats)
        for key in ("peak_bytes_in_use", "peak_bytes_reserved"))


def report(phase: str, meter: CompileMeter, seconds: float) -> None:
    print(f"[{phase}] wall_s={seconds:.2f} {meter.take()} {peak_bytes()}",
          flush=True)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def run_train(cfg, dist_mode: str):
    """A CodedTrainer run as the training launcher builds it."""
    from repro.models import build_model
    from repro.optim import OptConfig
    from repro.sim.traces import make_trace
    from repro.training import CodedTrainConfig, CodedTrainer

    trainer = CodedTrainer(
        build_model(cfg),
        CodedTrainConfig(code="frc", n_workers=8, s=2, decoder="onestep",
                         seq_len=SEQ_LEN, steps=STEPS, seed=SEED,
                         log_every=1, dist_mode=dist_mode,
                         opt=OptConfig(lr=1e-4, warmup_steps=1,
                                       total_steps=STEPS)),
        trace=make_trace("pareto", steps=STEPS, n=8, seed=SEED),
        sync_policy="deadline")
    out = trainer.run()
    for h in out["history"]:
        print(f"[train:{dist_mode}] step {h['step']} "
              f"mean_ce={h['mean_ce']!r} stragglers={h['stragglers']} "
              f"decode_err/k={h['decode_err']!r}", flush=True)
    return trainer, out


def check_train(history, vocab: int) -> None:
    ce = [h["mean_ce"] for h in history]
    check(all(math.isfinite(c) for c in ce), f"finite mean_ce {ce}")
    check(abs(ce[0] - math.log(vocab)) < 0.5,
          f"first mean_ce {ce[0]} near ln(vocab) = {math.log(vocab)}")
    check(all(math.isfinite(h["decode_err"]) for h in history),
          "decode_err logged")
    check(sum(h["stragglers"] for h in history) > 0,
          "the deadline dropped at least one worker")


def run_decode() -> None:
    """decode_batch on the device kernels against the numpy decoder."""
    from repro.core import registry
    from repro.core.engine import DecodeEngine

    n, batch = DECODE_N, DECODE_B
    code = registry.make("bgc", k=n, n=n, s=DECODE_S, seed=SEED)
    masks = np.random.default_rng(SEED).random((batch, n)) >= DECODE_DELTA
    ref = DecodeEngine(code, backend="numpy")
    ell = DecodeEngine(code, backend=DECODE_BACKEND)
    check(ell._use_ell(), "the sparse code takes the ELL route")
    cases = [("onestep/ell", ell, "onestep"),
             ("onestep/dense", DecodeEngine(code, backend=DECODE_BACKEND,
                                            sparse="never"), "onestep"),
             ("optimal", ell, "optimal"),
             ("algorithmic", ell, "algorithmic")]
    for label, engine, method in cases:
        got, want = engine.decode_batch(masks, method), \
            ref.decode_batch(masks, method)
        e_err = float(np.max(np.abs(got.errors - want.errors)
                             / np.maximum(np.abs(want.errors), 1.0)))
        w_err = float(np.max(np.abs(got.weights - want.weights))
                      / max(np.abs(want.weights).max(), 1.0))
        print(f"[decode] {label} B={batch} n={n} s={DECODE_S} "
              f"errors_rel={e_err!r} (tol {DECODE_ERR_RTOL}) "
              f"weights_rel={w_err!r} (tol {DECODE_W_TOL}) "
              f"mean_err={float(got.errors.mean())!r}", flush=True)
        check(got.weights.shape == (batch, n) and got.errors.shape
              == (batch,), f"{label} shapes")
        check(bool(np.isfinite(got.weights).all()
                   and np.isfinite(got.errors).all()), f"{label} finite")
        check(e_err <= DECODE_ERR_RTOL and w_err <= DECODE_W_TOL,
              f"{label} matches numpy within tolerance")


def run_serve(model, params) -> None:
    """serve_queue through the continuous-batching engine; each served
    token checked against the plain full forward (no cache)."""
    import jax.numpy as jnp

    from repro.models.lm import lm_forward
    from repro.serving import Request, ServingEngine

    cfg = model.cfg
    requests, slots = SERVE_REQUESTS, SERVE_SLOTS
    prompt_len, max_new = SERVE_PROMPT, SERVE_NEW
    engine = ServingEngine(model, params, batch_slots=slots,
                           cache_len=prompt_len + max_new + 8)
    rng = np.random.default_rng(SEED)
    reqs = [Request(rid=i, prompt=rng.integers(
        1, cfg.vocab, prompt_len).astype(np.int32), max_new_tokens=max_new)
        for i in range(requests)]
    results = engine.serve_queue(reqs)
    check(len(results) == requests and all(r.done for r in reqs),
          "every request done")
    check(all(len(results[r.rid]) == max_new for r in reqs),
          f"every request got {max_new} tokens")
    check(all(0 <= t < cfg.padded_vocab for v in results.values()
              for t in v), "token ids in range")

    forward = jax.jit(lambda p, b: lm_forward(p, cfg, b)[0])
    worst = 0.0
    for r in reqs[:2]:
        toks = np.concatenate([r.prompt, np.asarray(r.generated[:-1])])
        logits = np.asarray(forward(params, {"tokens": jnp.asarray(
            toks[None].astype(np.int32))})[0], dtype=np.float64)
        for t, tok in enumerate(r.generated):
            row = logits[prompt_len - 1 + t]
            gap = (row.max() - row[tok]) / (row.max() - row.mean())
            worst = max(worst, float(gap))
    print(f"[serve] {requests} requests x {max_new} tokens on {slots} "
          f"slots; worst served-token gap to the reference top logit "
          f"{worst!r} of the logit spread (tol {SERVE_GAP})", flush=True)
    check(worst <= SERVE_GAP, "served tokens are top logits of the "
          "full forward")


def run_four_chips(cfg) -> None:
    """coded_allreduce over the four chips against fused on the first."""
    devices = jax.devices()
    trainer, out = run_train(cfg, "coded_allreduce")
    ar = trainer.allreduce
    on = out["state"]["params"]["embed"].sharding.device_set
    print(f"[train:coded_allreduce] mesh {ar.n_devices} device(s) x "
          f"{ar.partition.lanes} lane(s); params on "
          f"{sorted(d.id for d in on)}", flush=True)
    check(ar.n_devices == 4 and ar.partition.lanes == 2
          and len({d.id for d in ar.mesh.devices.flat}) == 4
          and len(on) == 4, "the coded all-reduce spans 4 devices")
    dist = [h["mean_ce"] for h in out["history"]]
    check_train(out["history"], cfg.vocab)
    del trainer, out, ar, on
    gc.collect()       # two trainers' state do not fit one chip together
    print(f"[train] after freeing: bytes_in_use="
          f"{(devices[0].memory_stats() or {}).get('bytes_in_use', -1)} "
          "on device 0",
          flush=True)

    trainer, out = run_train(cfg, "fused")
    on = out["state"]["params"]["embed"].sharding.device_set
    check(on == {devices[0]}, "fused mode runs on the first chip")
    fused = [h["mean_ce"] for h in out["history"]]
    check_train(out["history"], cfg.vocab)
    rel = max(abs(a - b) / abs(b) for a, b in zip(dist, fused))
    print(f"[train] coded_allreduce vs fused mean_ce: max rel diff "
          f"{rel!r} (tol {DIST_RTOL})", flush=True)
    check(rel <= DIST_RTOL, "coded_allreduce matches fused")


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the coded all-reduce across four chips "
                         "and its fused comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.platform import enable_compile_cache

    cache = enable_compile_cache()
    meter = CompileMeter()
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, n_layers=LAYERS, loss_chunk=LOSS_CHUNK)
    print(f"[config] {ARCH}: d_model={cfg.d_model} heads={cfg.n_heads}x"
          f"{cfg.d_head} kv={cfg.n_kv} d_ff={cfg.d_ff} vocab={cfg.vocab} "
          f"params={cfg.param_count() / 1e6:.1f}M; reduced: n_layers "
          f"{full.n_layers}->{cfg.n_layers}; seq_len={SEQ_LEN} "
          f"loss_chunk={LOSS_CHUNK} steps={STEPS} seed={SEED}", flush=True)

    if args.chips == 4:
        t0 = time.perf_counter()
        run_four_chips(cfg)
        report("train:4chips", meter, time.perf_counter() - t0)
    else:
        t0 = time.perf_counter()
        trainer, out = run_train(cfg, "fused")
        check_train(out["history"], cfg.vocab)
        report("train", meter, time.perf_counter() - t0)
        model, params = trainer.model, out["state"]["params"]
        del trainer, out    # the optimizer state is not needed to serve
        gc.collect()

        t0 = time.perf_counter()
        run_decode()
        report("decode", meter, time.perf_counter() - t0)

        t0 = time.perf_counter()
        run_serve(model, params)
        report("serve", meter, time.perf_counter() - t0)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
