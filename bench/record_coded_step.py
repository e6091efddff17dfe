"""Record ``bench/data/coded_step.xplane.pb`` on one chip: two steps of
the coded trainer at small widths (cell ``minicpm-2b.coded-frc8``'s job
and model kinds, d_model 64), with the program's host spans and named
scopes, inside one ``bench.chunk`` window as the benchmark traces it.

    python3 bench/record_coded_step.py [--out bench/data/coded_step.xplane.pb]

Source labels in the trace name paths inside the checkout: the
checkout's own prefix is cut from them.  The ``/host:metadata`` plane
(the compiled programs' HLO protos, which no reduction reads) is left
out, to keep the file small.  Prints the cost of an inactive host span
(no profiler running) on this host.
"""

import argparse
import glob
import os
import re
import shutil
import sys
import tempfile
import timeit
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import harness, program_trace  # noqa: E402

DROPPED_PLANE = "/host:metadata"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def without_plane(data: bytes, name: str) -> bytes:
    """The serialized XSpace less its plane called ``name`` (every field
    of an XSpace is length-delimited)."""
    out = bytearray()
    for field, value in program_trace._fields(memoryview(data)):
        if field == 1 and any(f == 2 and program_trace._text(v) == name
                              for f, v in program_trace._fields(value)):
            continue
        out += _varint(field << 3 | 2) + _varint(len(value)) + bytes(value)
    return bytes(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(ROOT / "bench" / "data"
                                         / "coded_step.xplane.pb"))
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    import jax
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(ROOT) + os.sep))
    spec = harness.load_cell("minicpm-2b.coded-frc8")
    spec["config"]["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv=4,
                                   d_head=16, d_ff=128, vocab=503,
                                   vocab_pad_to=64, loss_chunk=16)
    spec["traffic"]["trainer"]["seq_len"] = 16
    trainer, state = harness.Cell(spec).build(args.seed)
    state = trainer.run(state, start_step=0, steps=1)["state"]   # compile

    from repro import tracing
    n = 100000
    idle = timeit.timeit(lambda: None, number=n) / n
    with_span = timeit.timeit(
        "with span(name): pass", number=n,
        globals={"span": tracing.span, "name": tracing.BATCH}) / n
    print(f"inactive span: {1e9 * (with_span - idle):.1f} ns "
          f"({1e9 * with_span:.1f} ns with the loop)")

    tdir = tempfile.mkdtemp(prefix="coded-step-trace-")
    jax.profiler.start_trace(tdir, profiler_options=harness._profile_options())
    with harness._annotate("bench.chunk"):
        state = trainer.run(state, start_step=1, steps=2)["state"]
    jax.profiler.stop_trace()
    src = max(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True), key=os.path.getmtime)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(src, "rb") as f:
        data = without_plane(f.read(), DROPPED_PLANE)
    with open(args.out, "wb") as f:
        f.write(data)
    shutil.rmtree(tdir, ignore_errors=True)
    red = program_trace.reduce_file(args.out, n_devices=1)
    print(f"{args.out}: {os.path.getsize(args.out)} bytes")
    print(red)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
