"""Names of the program's marks in a JAX profiler trace.

Host spans: each phase of ``CodedTrainer.run``'s loop body is a
``jax.profiler.TraceAnnotation`` named ``repro.train.<phase>``.  Spans
are leaves: none encloses another, and no span encloses a whole step, so
that a span names the host work it covers (the profiler's reader names a
device idle gap by the host event that covers most of it).  When no
profiler runs, a span costs one inactive TraceMe.

Device scopes: ``jax.named_scope`` around the layers of the model step.
A scope lands in the ``op_name`` metadata of every HLO instruction
traced inside it (the ``tf_op`` stat of the op's event in the trace);
backward ops carry their forward scope, wrapped as ``transpose(jvp(..))``
where JAX transformed it.  Scopes are metadata only: the compiled
program is the same with or without them.

To capture a window, wrap the calls in ``jax.profiler.trace(log_dir)``;
docs/architecture.md ("Tracing") lists what each name covers.
"""

from __future__ import annotations

import jax

__all__ = ["DECODE", "BATCH", "H2D", "DISPATCH", "LOG", "RECODE", "CKPT",
           "HOST_SPANS", "ATTENTION", "MLP", "HEAD_CE", "OPTIMIZER",
           "SCOPES", "MOE_DISPATCH", "MOE_EXPERTS", "MOE_SCOPES", "span"]

# host spans of CodedTrainer.run, one leaf each
DECODE = "repro.train.decode"        # straggler mask + decode weights
BATCH = "repro.train.batch"          # the coded batch, on the host
H2D = "repro.train.h2d"              # its transfer (the host's side)
DISPATCH = "repro.train.dispatch"    # the jitted step's call (async)
LOG = "repro.train.log"              # the log record and its device read
RECODE = "repro.train.recode"        # elastic/churn/controller rebuilds
CKPT = "repro.train.ckpt"            # a checkpoint save
HOST_SPANS = (DECODE, BATCH, H2D, DISPATCH, LOG, RECODE, CKPT)

# named scopes of the model step
ATTENTION = "attention"   # ln1 .. the wo projection and residual add
MLP = "mlp"               # ln2 .. the MLP (or MoE) and residual add
HEAD_CE = "head_ce"       # final norm, head and cross-entropy
OPTIMIZER = "optimizer"   # AdamW, global-norm clipping included
SCOPES = (ATTENTION, MLP, HEAD_CE, OPTIMIZER)
# inside MLP, under dropless MoE dispatch (models/moe.py)
MOE_DISPATCH = "moe_dispatch"  # router, top-k, gates and per-row aux
MOE_EXPERTS = "moe_experts"    # the held experts' matmuls, gates applied
MOE_SCOPES = (MOE_DISPATCH, MOE_EXPERTS)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span; no keyword arguments, so the event's name is
    ``name`` itself."""
    return jax.profiler.TraceAnnotation(name)
