"""Coded training loop: gradient coding as a first-class data-parallel
feature.

Per step:
  1. the straggler model samples a non-straggler mask (deterministic in
     (seed, step) -> derived identically on every host, no communication);
  2. the decoder turns (G, mask) into decode weights w;
  3. the pipeline materializes the physical batch with per-row loss
     weights  w_j * G[i,j] / (k*T)  — the decode-as-loss-reweighting
     identity (docs/architecture.md §2.1), so XLA's ordinary gradient all-reduce IS
     the coded aggregation;
  4. one jitted train_step (grad + AdamW) under the active mesh.

Elasticity: on hard faults the worker set shrinks, the code is rebuilt
for n' (O(n s)), the assignment/pipeline remapped, and training continues
without losing optimizer state.

Membership churn: pass ``churn=`` (a sim.traces.ChurnScenario) and worker
arrival/departure becomes a trained-through event — departures shrink
through the elastic path above (or, under ``recovery='restart'``, restore
the last checkpoint onto the post-event fleet and recompute the lost
steps), arrivals grow through the same rebuild, and the data pipeline
reshards without dropping or double-counting a shard (the stream is pure
in (seed, step, task)).  Checkpoints carry code/controller/churn metadata
so a killed-then-restarted run equals an uninterrupted one
(docs/architecture.md §11).

Co-simulation hook: pass ``trace=`` (a sim.traces.LatencyTrace) and the
trainer derives each step's straggler mask from the trace through a sync
policy (``sync_policy=``, default a 1.5s deadline) instead of the
straggler model, and logs the modelled wall-clock per step
(``step_time`` / cumulative ``sim_time`` in history) — the ClusterSim
dataflow riding the real training loop.

Adaptive control: pass ``controller=`` (a ``repro.control.AdaptiveCoder``
or anything with its observe/decide protocol) and the trainer feeds the
controller each step's mask / latencies / realized decode error, then
applies the actions it returns — ``set_s`` re-codes through the elastic
rebuild path (code, assignment, pipeline, engine, allreduce, step_fn),
``set_decoder`` / ``set_deadline`` recompute the trace schedule.  The
system picks its own operating point on the paper's frontier
(docs/adaptive.md).

Pipelined decoding: ``staleness=1`` removes the per-step decode barrier —
step t applies the weights decoded from step t-1's mask (re-masked by
today's stragglers, whose messages never arrived) and today's decode is
issued after the async step dispatch, overlapping the backprop.  Step 0
warm-starts from an all-alive decode; elastic re-codes, ``set_s`` and
``set_decoder`` flush the in-flight weights (docs/architecture.md §10).

Distributed execution: ``dist_mode="coded_allreduce"`` replaces step 3-4
with the shard_map path of ``dist.coded_allreduce`` (docs/architecture.md §9): the
batch is sliced into per-device microbatches (each device computes only
its workers' assigned task-gradients), and decoding happens as the
weighted psum over the 1-D worker mesh.  With a trace attached, the
whole run's masks are mapped through the policy up front and decoded in
ONE DecodeEngine.decode_batch call (the ClusterSim invariant); per-step
weights are then row lookups.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from ..core import assignment as ASG
from ..core import decoding as DEC
from ..core import registry as REG
from ..core.engine import DecodeEngine
from ..data import CodedDataPipeline, PipelineConfig
from ..dist import use_mesh
from ..models import Model
from ..optim import OptConfig, adamw_update, init_opt_state, make_schedule
from ..runtime import FaultInjector, StragglerModel, NoStragglers
from .. import tracing

__all__ = ["CodedTrainConfig", "CodedTrainer", "explicit_master_decode_grads"]


@dataclasses.dataclass
class CodedTrainConfig:
    code: str = "bgc"            # any core.registry family name
    code_params: dict = dataclasses.field(default_factory=dict)
    #   family extras (e.g. sbm blocks/intra) — forwarded to the
    #   constructor on every (re)build, elastic re-codes included
    n_workers: int = 8           # number of DP groups (paper's n); k = n
    s: int = 2                   # tasks per worker
    decoder: str = "onestep"     # onestep | optimal | algorithmic | ignore
    decoder_iters: int = 4       # algorithmic decoder iterations
    rows_per_slot: int = 1       # T examples per task slot
    seq_len: int = 128
    steps: int = 50
    seed: int = 0
    opt: OptConfig = dataclasses.field(default_factory=OptConfig)
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0
    keep_last: int = 2
    log_every: int = 10
    exact_decode_renorm: bool = True  # rescale w so sum(G@w)=k (unbiased-ish)
    decode_cache_size: int = 512      # mask->weights LRU entries (engine)
    dist_mode: str = "fused"          # fused | coded_allreduce (docs/architecture.md §9)
    optimal_impl: str = "auto"        # least-squares strategy (engine):
    #   auto/gram = masked-Gram normal equations (fast default);
    #   pinv = exact min-norm pinv, the exact-oracle opt-in
    staleness: int = 0                # decode pipelining depth: step t
    #   applies weights decoded from step t-staleness's mask (masked by
    #   today's stragglers), overlapping decode with backprop.  0 =
    #   synchronous.  Stale weights flush on elastic re-code / set_s /
    #   set_decoder (docs/architecture.md §10).


class CodedTrainer:
    def __init__(self, model: Model, tcfg: CodedTrainConfig,
                 straggler_model: Optional[StragglerModel] = None,
                 fault_injector: Optional[FaultInjector] = None,
                 mesh=None, trace=None, sync_policy=None,
                 controller=None, churn=None, recovery: str = "elastic"):
        self.model = model
        self.tcfg = tcfg
        self.straggler = straggler_model or NoStragglers()
        self.faults = fault_injector or FaultInjector()
        self.mesh = mesh
        # AdaptiveCoder protocol (repro.control): observe(step, mask,
        # latencies, decode_err) each step, decide(step) at the top of
        # the next one; returned actions are applied through the same
        # rebuild path as elastic faults (docs/adaptive.md)
        self.controller = controller
        if tcfg.dist_mode not in ("fused", "coded_allreduce"):
            raise ValueError(f"dist_mode {tcfg.dist_mode!r} not in "
                             f"('fused', 'coded_allreduce')")
        if tcfg.dist_mode == "coded_allreduce" and mesh is not None:
            from ..dist.coded_allreduce import WORKER_AXIS
            if WORKER_AXIS not in getattr(mesh, "axis_names", ()):
                raise ValueError(
                    "dist_mode='coded_allreduce' with mesh= needs a mesh "
                    f"carrying the {WORKER_AXIS!r} axis (see "
                    "dist.sharding.make_coded_mesh); got axes "
                    f"{tuple(getattr(mesh, 'axis_names', ()))}")
        if tcfg.staleness < 0:
            raise ValueError(f"staleness must be >= 0, got {tcfg.staleness}")
        # code builds draw from a counter-derived rng stream so the N-th
        # (re)build is deterministic in (seed, N): a restored run rebuilds
        # bit-identical codes (see maybe_restore) and an elastic/churn
        # re-code is reproducible across trainer instances
        self._builds = 0
        # trace-driven co-simulation (sim.cluster): trace rows -> masks +
        # modelled step times through a sync policy
        self.trace = trace
        self.sync_policy = None
        self._policy_state = None
        self.sim_time = 0.0
        # membership churn (sim.traces.ChurnScenario): worker arrival /
        # departure trained through — departures shrink-re-code (or
        # restore a checkpoint under recovery='restart'), arrivals grow
        self.churn = churn
        self.recovery = recovery
        self._live_ids = None
        self._churn_cursor = 0
        self.churn_log: list = []
        if churn is not None:
            if trace is not None:
                raise ValueError("churn= and trace= are exclusive: a "
                                 "ChurnScenario carries its own latency "
                                 "trace")
            if recovery not in ("elastic", "restart"):
                raise ValueError(f"recovery {recovery!r} not in "
                                 f"('elastic', 'restart')")
            if recovery == "restart" and not (tcfg.ckpt_dir
                                              and tcfg.ckpt_every):
                raise ValueError("recovery='restart' needs ckpt_dir and "
                                 "ckpt_every (restores the last checkpoint "
                                 "on membership change)")
            if churn.n0 != tcfg.n_workers:
                raise ValueError(f"churn scenario starts with n0="
                                 f"{churn.n0} workers, config has "
                                 f"n_workers={tcfg.n_workers}")
            self._live_ids = churn.initial_ids()
            from ..sim.cluster import make_policy
            self.sync_policy = make_policy(sync_policy or "deadline")
        elif trace is not None:
            from ..sim.cluster import make_policy
            if trace.n != tcfg.n_workers:
                raise ValueError(f"trace has n={trace.n} workers, config "
                                 f"has n_workers={tcfg.n_workers}")
            self.sync_policy = make_policy(sync_policy or "deadline")
            if controller is not None:
                from ..sim.cluster import DeadlinePolicy
                if not isinstance(self.sync_policy, DeadlinePolicy):
                    # the controller prices/emits set_deadline actions;
                    # silently dropping them would desync its tracked
                    # operating point from the trainer's reality
                    raise ValueError(
                        "controller= with trace= requires a DeadlinePolicy "
                        f"sync policy (its deadline is a controller "
                        f"actuator); got {type(self.sync_policy).__name__}")
        elif sync_policy is not None:
            raise ValueError("sync_policy requires trace= or churn=")
        self._build_code(tcfg.n_workers)
        self._step_fn = self._make_step_fn()
        self.history: list = []
        # per-step applied decode weights (the staleness tests assert
        # the staleness=0 stream is bitwise the synchronous stream)
        self.weight_log: list = []

    def _mask_and_time(self, step: int, n: int):
        """(mask, modelled step time | None) — trace-driven when a trace
        is attached, else the straggler model with no time model."""
        if self.churn is not None:
            # latencies of the LIVE capacity slots, speed-scaled; the
            # policy sees an n-wide fleet whose identity churns
            lat = self.churn.latencies_at(step, self._live_ids)
            mask, t, self._policy_state = self.sync_policy.step(
                lat, self._policy_state)
            self.sim_time += t
            return mask, t
        if self.trace is None:
            return self.straggler.sample(step, n), None
        if self._trace_masks is not None:   # dist path: precomputed schedule
            i = step % self._trace_masks.shape[0]
            t = float(self._trace_times[i])
            self.sim_time += t
            return self._trace_masks[i], t
        lat = self.trace.latencies[step % self.trace.steps]
        if n != lat.shape[0]:   # elastic shrink: simulate surviving workers
            lat = lat[:n]
        mask, t, self._policy_state = self.sync_policy.step(
            lat, self._policy_state)
        self.sim_time += t
        return mask, t

    # ------------- code / assignment / pipeline -------------
    def _build_code(self, n: int) -> None:
        t = self.tcfg
        fam = REG.get(t.code)     # actionable KeyError on unknown schemes
        fam.require_decoder(t.decoder)
        rng = np.random.default_rng([t.seed, 0xC0DE, self._builds])
        self._builds += 1
        self.code = fam.make(k=n, n=n, s=min(t.s, n), rng=rng,
                             **t.code_params)
        # one engine per live code; rebuilt (cache and all) on elastic
        # re-coding since the weights are a function of G
        self.engine = DecodeEngine(self.code, iters=t.decoder_iters,
                                   cache_size=t.decode_cache_size,
                                   optimal_impl=t.optimal_impl)
        self.assignment = ASG.build_assignment(self.code)
        if getattr(self, "pipeline", None) is not None:
            # reshard: same (seed, step, task)-pure stream, new layout —
            # no shard dropped or double-counted across the re-code
            self.pipeline = self.pipeline.reshard_for(self.assignment)
        else:
            self.pipeline = CodedDataPipeline(
                self.assignment,
                PipelineConfig(vocab=self.model.cfg.vocab, seq_len=t.seq_len,
                               rows_per_slot=t.rows_per_slot, seed=t.seed))
        self.allreduce = None
        self._trace_masks = self._trace_times = self._trace_weights = None
        # elastic re-code invalidation: weights decoded against the OLD
        # G are meaningless for the new code — drop the whole pipeline
        # (the next step warm-starts from an all-alive decode)
        self._pending_w = None
        if t.dist_mode == "coded_allreduce":
            from ..dist.coded_allreduce import CodedAllReduce
            kw = {"mesh": self.mesh} if self.mesh is not None else {}
            self.allreduce = CodedAllReduce(
                self.code, engine=self.engine, assignment=self.assignment,
                **kw)
            if self.trace is not None:
                self._prepare_trace_schedule()

    def _prepare_trace_schedule(self) -> None:
        """Distributed path: map the WHOLE trace through the sync policy
        and decode every step's mask in ONE decode_batch call (the
        ClusterSim invariant — ``engine.batch_calls`` advances by 1 per
        trace/engine, never once per step).  Recomputed on elastic
        re-coding since the engine is rebuilt with the code."""
        lat = self.trace.latencies
        n = self.assignment.n
        if lat.shape[1] != n:   # elastic shrink: surviving workers
            lat = lat[:, :n]
        masks, times, _ = self.sync_policy.apply(lat)
        self._trace_masks = masks
        self._trace_times = times
        self._trace_weights = self.allreduce.weights_for_masks(
            masks, method=self.tcfg.decoder,
            renorm=self.tcfg.exact_decode_renorm)

    # ------------- adaptive re-coding (repro.control) -------------
    def _apply_action(self, action) -> None:
        """Apply one controller action (docs/adaptive.md).

        ``set_s`` rebuilds code / assignment / pipeline / engine /
        allreduce AND the jitted step_fn — exactly the elastic-fault
        path, so partition-derived closures (ce_fix, D) can never go
        stale.  ``set_decoder`` / ``set_deadline`` leave the code alone
        (no resample) but recompute the distributed trace schedule,
        whose masks/weights depend on both.
        """
        t = self.tcfg
        if action.kind == "set_s":
            self.tcfg = dataclasses.replace(t, s=int(action.value))
            self._build_code(self.assignment.n)
            self._step_fn = self._make_step_fn()
            return
        if action.kind == "set_decoder":
            decoder = str(action.value)
            REG.get(t.code).require_decoder(decoder)
            self.tcfg = dataclasses.replace(t, decoder=decoder)
            self._pending_w = None   # in-flight weights used the old decoder
            if self._trace_masks is not None:
                self._prepare_trace_schedule()
            return
        if action.kind == "set_deadline":
            from ..sim.cluster import DeadlinePolicy
            if isinstance(self.sync_policy, DeadlinePolicy):
                self.sync_policy = dataclasses.replace(
                    self.sync_policy, deadline=float(action.value))
                if self._trace_masks is not None:
                    self._prepare_trace_schedule()
            # without a trace no latencies are observed, so controllers
            # never emit deadline actions; the trace+non-deadline-policy
            # combination is rejected in __init__
            return
        raise ValueError(f"unknown controller action kind {action.kind!r}")

    # ------------- jitted step -------------
    def _make_step_fn(self) -> Callable:
        model, opt_cfg = self.model, self.tcfg.opt
        sched = make_schedule(opt_cfg.schedule
                              if model.cfg.schedule == "cosine"
                              else model.cfg.schedule,
                              opt_cfg.lr, opt_cfg.total_steps,
                              opt_cfg.warmup_steps, opt_cfg.min_ratio,
                              opt_cfg.decay_frac)

        if self.tcfg.dist_mode == "coded_allreduce":
            vg = self.allreduce.value_and_grad(model.loss_fn, jit=False)
            part = self.allreduce.partition
            D = part.n_devices
            # padding-lane rows are masked out of the per-row CE (see
            # device_batch_for_step) but still counted by row.mean();
            # padded_n/n undoes the dilution so mean_ce matches fused
            ce_fix = part.padded_n / part.n

            def step_fn(params, opt_state, batch):
                (loss, metrics), grads = vg(params, batch)
                # psum sums scalar aux over devices: means divide back
                metrics = dict(metrics)
                for key in ("mean_ce", "aux_loss"):
                    if key in metrics:
                        metrics[key] = metrics[key] / D
                if "mean_ce" in metrics:
                    metrics["mean_ce"] = metrics["mean_ce"] * ce_fix
                lr = sched(opt_state["step"])
                params, opt_state, om = adamw_update(params, grads, opt_state,
                                                     opt_cfg, lr)
                metrics = dict(metrics, **om)
                return params, opt_state, metrics

            return jax.jit(step_fn, donate_argnums=(0, 1))

        def step_fn(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                model.loss_fn, has_aux=True)(params, batch)
            lr = sched(opt_state["step"])
            params, opt_state, om = adamw_update(params, grads, opt_state,
                                                 opt_cfg, lr)
            metrics = dict(metrics, **om)
            return params, opt_state, metrics

        return jax.jit(step_fn, donate_argnums=(0, 1))

    # ------------- decode weights -------------
    def decode_weights_for(self, mask: np.ndarray) -> np.ndarray:
        """mask -> decode weights via the engine's LRU cache.

        Repeated masks (adversarial stragglers, stable deadline cohorts,
        the no-straggler fast path) decode once per distinct mask.
        """
        t = self.tcfg
        w = self.engine.decode(mask, method=t.decoder)
        if t.exact_decode_renorm:
            w = DEC.exact_decode_renorm(self.code.G, w)
        return w

    # ------------- state init / restore -------------
    def init_state(self, rng_key=None):
        key = jax.random.PRNGKey(self.tcfg.seed) if rng_key is None else rng_key
        params = self.model.init(key)
        opt_state = init_opt_state(params)
        return {"params": params, "opt": opt_state}

    def maybe_restore(self, state):
        """Restore the latest checkpoint under ckpt_dir, if any.

        Applies the checkpoint's metadata, not just its arrays: the code
        is rebuilt at the checkpointed (family, params, s, n, decoder)
        operating point — and at the checkpointed build counter, so the
        rebuilt G is bit-identical to the one the interrupted run was
        using — the churn cursor / live worker set / sim clock resume,
        and the controller reloads its estimator state.  A restored run
        is therefore equal to an uninterrupted one, which is what the
        restart-recovery equivalence test asserts.
        """
        t = self.tcfg
        if not (t.ckpt_dir and latest_step(t.ckpt_dir) is not None):
            return state, 0
        state, meta = restore_checkpoint(t.ckpt_dir, state)
        code_meta = meta.get("code")
        if code_meta:
            self.tcfg = dataclasses.replace(
                t, code=str(code_meta["family"]),
                code_params=dict(code_meta.get("params", {})),
                s=int(code_meta["s"]), decoder=str(code_meta["decoder"]))
            # rewind the build counter so the rebuild replays the exact
            # rng draw the checkpointed code came from
            self._builds = max(int(code_meta.get("builds", 1)) - 1, 0)
            self._build_code(int(code_meta["n"]))
            self._step_fn = self._make_step_fn()
        self.sim_time = float(meta.get("sim_time", self.sim_time))
        if self.churn is not None and "live_ids" in meta:
            self._live_ids = np.asarray(meta["live_ids"], dtype=np.int64)
            self._churn_cursor = int(meta.get("churn_cursor", 0))
        ctrl_meta = meta.get("controller")
        if ctrl_meta and hasattr(self.controller, "load_state_dict"):
            self.controller.load_state_dict(ctrl_meta)
        return state, int(meta.get("next_step", 0))

    def _ckpt_metadata(self, next_step: int) -> dict:
        """Everything a fresh process needs to resume equal to an
        uninterrupted run (see maybe_restore)."""
        live = self.tcfg
        meta = {
            "next_step": int(next_step),
            "code": {"family": live.code,
                     "params": dict(live.code_params),
                     "s": int(self.code.s),
                     "n": int(self.assignment.n),
                     "decoder": live.decoder,
                     "builds": int(self._builds)},
            "sim_time": float(self.sim_time),
        }
        if self.churn is not None:
            meta["live_ids"] = [int(i) for i in self._live_ids]
            meta["churn_cursor"] = int(self._churn_cursor)
        if self.controller is not None and hasattr(self.controller,
                                                   "state_dict"):
            meta["controller"] = self.controller.state_dict()
        return meta

    # ------------- churn events -------------
    def _consume_churn(self, step: int, state, ckpt):
        """Apply every scenario event scheduled at `step` (top-of-step).

        The cursor is monotonic: events consumed once never reapply, so
        a restart rewind replays *steps* (recomputing lost work on the
        current fleet) without replaying *events*.  Departures shrink
        the fleet — elastic re-code, or checkpoint restore + rewind
        under recovery='restart' (gang-scheduling semantics: ANY
        membership change restarts the job).  Arrivals grow through the
        same rebuild path.  Returns (state, step, recoded).
        """
        events = self.churn.events
        fired = []
        while (self._churn_cursor < len(events)
               and events[self._churn_cursor].step <= step):
            # a restart rewind leaves the cursor PAST the triggering
            # event, so replayed steps reach here with nothing to fire
            fired.append(events[self._churn_cursor])
            self._churn_cursor += 1
        if not fired:
            return state, step, False
        with tracing.span(tracing.RECODE):
            state, step = self._apply_churn(fired, step, state, ckpt)
        return state, step, True

    def _apply_churn(self, fired, step: int, state, ckpt):
        live = self._live_ids
        for ev in fired:
            live = self.churn.apply_event(live, ev)
            self.churn_log.append({"step": step, "kind": ev.kind,
                                   "n_live": int(live.size)})
        if live.size < 2:
            raise RuntimeError(f"churn left {live.size} worker(s) alive at "
                               f"step {step}; need >= 2")
        self._live_ids = live
        if self.recovery == "restart":
            # the new incarnation restores the last checkpoint (or cold
            # starts) on the post-event fleet and recomputes lost steps;
            # the (seed, step, task)-pure pipeline makes the redo exact
            if ckpt is not None:
                ckpt.wait()   # in-flight saves land before we look
            if latest_step(self.tcfg.ckpt_dir) is not None:
                state, meta = restore_checkpoint(self.tcfg.ckpt_dir, state)
                step = int(meta.get("next_step", 0))
            else:
                state = self.init_state()
                step = 0
            self.churn_log[-1]["restart_to"] = step
        self._build_code(len(self._live_ids))
        self._step_fn = self._make_step_fn()
        return state, step

    # ------------- main loop -------------
    def run(self, state=None, start_step: int = 0,
            steps: Optional[int] = None) -> Dict[str, Any]:
        t = self.tcfg
        if state is None:
            state = self.init_state()
        if start_step == 0:
            # fires for explicitly-passed state too: a fresh process
            # handed init_state() must still resume from ckpt_dir (the
            # old `state is None` guard silently restarted from scratch)
            state, start_step = self.maybe_restore(state)
            t = self.tcfg   # maybe_restore may have applied code metadata
        # default = finish the configured job: a restored run completes
        # the REMAINING steps (explicit steps= keeps count semantics)
        steps = max(t.steps - start_step, 0) if steps is None else steps
        ckpt = (AsyncCheckpointer(t.ckpt_dir, t.keep_last)
                if t.ckpt_dir and t.ckpt_every else None)
        n0 = self.assignment.n

        step = start_step
        end = start_step + steps
        with use_mesh(self.mesh):
            while step < end:
                # --- membership churn -> elastic re-code / restart ---
                if self.churn is not None:
                    state, step, _ = self._consume_churn(step, state, ckpt)

                # --- hard faults -> elastic re-code ---
                plan = self.faults.check(step)
                if plan is not None:
                    with tracing.span(tracing.RECODE):
                        alive = self.faults.alive_count(n0)
                        self._build_code(max(alive, 2))
                        # step_fn closures capture partition-derived
                        # scalars (ce_fix, D) — rebuild with the new code
                        self._step_fn = self._make_step_fn()

                # --- controller decision -> adaptive re-code ---
                if self.controller is not None:
                    action = self.controller.decide(step)
                    if action is not None:
                        with tracing.span(tracing.RECODE):
                            self._apply_action(action)

                # --- straggler mask -> decode weights -> coded batch ---
                with tracing.span(tracing.DECODE):
                    mask, step_time = self._mask_and_time(step,
                                                          self.assignment.n)
                    deferred = None
                    if t.staleness > 0:
                        # pipelined: apply weights decoded `staleness`
                        # steps ago, re-masked by TODAY's stragglers
                        # (their messages never arrived); today's decode
                        # is issued after the jitted step dispatch so it
                        # overlaps the backprop (docs/architecture.md §10)
                        if self._pending_w is None:  # warm start / flush
                            ones = np.ones(self.assignment.n, dtype=bool)
                            self._pending_w = [self.decode_weights_for(ones)
                                               ] * t.staleness
                        w = self._pending_w.pop(0) * mask
                        deferred = mask
                    elif self._trace_weights is not None:
                        w = self._trace_weights[
                            step % self._trace_weights.shape[0]]
                    else:
                        w = self.decode_weights_for(mask)
                    self.weight_log.append(np.array(w))

                if self.controller is not None:
                    # realized decode error of the weights in effect —
                    # the calibration signal closing the control loop
                    derr = float(((self.code.G @ w - 1.0) ** 2).sum()
                                 ) / self.code.k
                    lat = None
                    if self.churn is not None:
                        lat = self.churn.latencies_at(step, self._live_ids)
                    elif self.trace is not None:
                        lat = self.trace.latencies[step % self.trace.steps]
                        lat = lat[:mask.shape[0]]
                    self.controller.observe(step, mask, latencies=lat,
                                            decode_err=derr)
                if self.allreduce is not None:
                    with tracing.span(tracing.BATCH):
                        batch_np = self.pipeline.device_batch_for_step(
                            step, w, self.allreduce.partition)
                    with tracing.span(tracing.H2D):
                        batch = self.allreduce.shard_batch(batch_np)
                else:
                    with tracing.span(tracing.BATCH):
                        batch_np = self.pipeline.batch_for_step(step, w)
                    with tracing.span(tracing.H2D):
                        batch = {k: jnp.asarray(v)
                                 for k, v in batch_np.items()}

                with tracing.span(tracing.DISPATCH):
                    state["params"], state["opt"], metrics = self._step_fn(
                        state["params"], state["opt"], batch)

                if deferred is not None:
                    # decode of step t's own mask, issued while the step
                    # above executes asynchronously — consumed at t+st.
                    # The trace-schedule path reuses its precomputed row
                    # (still ONE decode_batch per trace)
                    with tracing.span(tracing.DECODE):
                        if self._trace_weights is not None:
                            S = self._trace_weights.shape[0]
                            self._pending_w.append(
                                self._trace_weights[step % S])
                        else:
                            self._pending_w.append(
                                self.decode_weights_for(deferred))

                if step % max(t.log_every, 1) == 0 or step == end - 1:
                    with tracing.span(tracing.LOG):
                        self._log(step, metrics, mask, step_time)

                if ckpt and t.ckpt_every and (step + 1) % t.ckpt_every == 0:
                    with tracing.span(tracing.CKPT):
                        ckpt.save(step + 1, state,
                                  self._ckpt_metadata(step + 1))

                step += 1

        if ckpt:
            ckpt.close()
        return {"state": state, "history": self.history,
                "final_step": end}

    def _log(self, step: int, metrics: dict, mask: np.ndarray,
             step_time) -> None:
        """Append the step's history record: one device read of its
        scalars."""
        # read the LIVE config: controller actions may have replaced
        # self.tcfg since the loop started
        live = self.tcfg
        loss, mean_ce, grad_norm = jax.device_get(
            (metrics["loss"], metrics["mean_ce"], metrics["grad_norm"]))
        rec = {"step": step,
               "loss": float(loss),
               "mean_ce": float(mean_ce),
               "grad_norm": float(grad_norm),
               "stragglers": int((~mask).sum()),
               "decode_err": float(
                   DEC.err1(self.code.G[:, mask],
                            DEC.default_rho(self.code.k, int(mask.sum()),
                                            self.code.s))
                   if live.decoder == "onestep" else
                   DEC.err(self.code.G[:, mask])) / self.code.k,
               "n_workers": self.assignment.n,
               "s": self.code.s,
               "decoder": live.decoder}
        if step_time is not None:
            rec["step_time"] = float(step_time)
            rec["sim_time"] = float(self.sim_time)
        self.history.append(rec)


def explicit_master_decode_grads(model: Model, params, trainer: CodedTrainer,
                                 step: int, mask: np.ndarray):
    """Reference implementation of the paper's master-side decode.

    Computes each worker's coded partial gradient SEPARATELY (sum over its
    assigned task shards with G coefficients), then combines them with the
    decode weights on the 'master' — the literal Algorithm-1/2 dataflow.
    Used by tests to prove the fused loss-reweighting path is identical.
    """
    t = trainer.tcfg
    asg = trainer.assignment
    w = trainer.decode_weights_for(mask)
    batch = trainer.pipeline.batch_for_step(step, np.ones(asg.n))
    T = t.rows_per_slot
    rows_per_worker = asg.slots * T

    def worker_loss(params, j):
        lo = j * rows_per_worker
        sl = {k: jnp.asarray(v[lo: lo + rows_per_worker])
              for k, v in batch.items()}
        # per-row coefficients G[i,j] / (k*T): the worker's coded combo
        coeff = np.repeat(
            np.where(asg.task_ids[j] >= 0, asg.coeffs[j], 0.0), T) / (asg.k * T)
        sl["loss_weight"] = jnp.asarray(coeff)  # f64 host-side; the model
        # casts at the device boundary (f32 unless x64 is enabled)
        loss, _ = model.loss_fn(params, sl)
        return loss

    partials = [jax.grad(worker_loss)(params, j) for j in range(asg.n)]
    # promote to at least fp32 but follow fp64 grads (x64 differential
    # tests compare the shard_map path against this oracle at 1e-10)
    flat = [jnp.concatenate(
        [g.reshape(-1).astype(jnp.promote_types(g.dtype, jnp.float32))
         for g in jax.tree_util.tree_leaves(p)])
            for p in partials]
    stacked = jnp.stack(flat)                      # [n, P]
    decoded = jnp.asarray(w, stacked.dtype) @ stacked
    return decoded, w
