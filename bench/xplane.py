"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read: per chip the busy time (the union of the
intervals in which an XLA operation runs), the device time of each
jitted program, collective time and the part of it during which no
other operation runs, and a breakdown of the longest operations and of
the longest idle gaps by what the host was doing meanwhile.

The traced window runs from the start of the first ``bench.chunk`` host
annotation to the end of the last one.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
# ops that contain other ops of the same line (a loop's body runs inside
# its while op): busy time, but neither "other work" nor a top op
CONTAINER = {"while", "conditional", "call"}
WINDOW_SPAN = "bench.chunk"
BENCH_SPAN = "bench."

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """a minus b; both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def _events(line) -> List[Tuple[float, float, str]]:
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def op_name(text: str) -> Tuple[str, str]:
    """An XLA Ops event is named by its HLO text,
    '%fusion.12 = (bf16[..]..) fusion(...), ...': returns ('fusion.12',
    'fusion'), the instruction and its opcode."""
    head, _, rest = text.partition(" = ")
    m = re.search(r"[\]}) ]([a-z][a-z0-9-]*)\(", rest)
    return head.lstrip("%"), (m.group(1) if m else head.lstrip("%"))


def device_planes(pd, n_devices: int):
    """The TensorCore planes of the first ``n_devices`` chips."""
    planes = []
    for p in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", p.name)
        if m:
            planes.append((int(m.group(1)), p))
    planes.sort(key=lambda t: t[0])
    return [p for _, p in planes[:n_devices]]


def module_key(name: str) -> str:
    """'jit_step_fn(12)' -> 'step_fn': a jitted program's stable name."""
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"^(jit_|pjit_)", "", name)


def reduce_profile(pd, n_devices: int) -> Dict:
    host = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                host.extend(_events(line))
    spans = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} annotation in the trace")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    window = hi - lo

    busy_s, coll_s, exposed_s = [], [], []
    module_s: Dict[str, List[float]] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    first_gaps: List[Interval] = []
    planes = device_planes(pd, n_devices)
    if not planes:
        raise ValueError("no /device:TPU:<n> plane in the trace; planes: "
                         + str([(p.name, [ln.name for ln in p.lines])
                                for p in pd.planes]))
    for i, plane in enumerate(planes):
        lines = {line.name: _events(line) for line in plane.lines}
        ops = [(max(s, lo), min(e, hi)) + op_name(n) for s, e, n in
               lines.get("XLA Ops", []) if e > lo and s < hi]
        busy = union([(s, e) for s, e, _, _ in ops])
        busy_s.append(total(busy))
        coll = union([(s, e) for s, e, _, k in ops if COLLECTIVE.match(k)])
        other = union([(s, e) for s, e, _, k in ops
                       if not COLLECTIVE.match(k) and k not in CONTAINER])
        coll_s.append(total(coll))
        exposed_s.append(total(subtract(coll, other)))
        per_mod: Dict[str, float] = defaultdict(float)
        for s, e, n in lines.get("XLA Modules", []):
            if e > lo and s < hi:
                per_mod[module_key(n)] += min(e, hi) - max(s, lo)
        for k, v in per_mod.items():
            module_s[k].append(v)
        for s, e, n, k in ops:
            if k not in CONTAINER:
                op_time[f"{n} {k}" if not n.startswith(k) else n] += \
                    (e - s) / len(planes)
        if i == 0:
            first_gaps = gaps(busy, lo, hi)

    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    named = []
    for s, e in sorted(first_gaps, key=lambda g: g[0] - g[1])[:10]:
        named.append([host_activity(host, s, e), e - s])
    return {"window_s": window,
            "busy_s": sum(busy_s) / len(busy_s),
            "busy_per_device_s": busy_s,
            "collective_s": coll_s,
            "collective_exposed_s": exposed_s,
            "module_s": dict(module_s),
            "breakdown": {"device_ops": [[n, t] for n, t in top_ops],
                          "idle_gaps": named}}


def host_activity(host, s: float, e: float) -> str:
    """What the host was doing while a chip sat idle over [s, e]: the
    host event that overlaps the gap most, the benchmark's own spans
    only where no other event does (the program's Python is untraced)."""
    best, best_key = "host: no event", None
    for hs, he, name in host:
        cover = min(he, e) - max(hs, s)
        if cover <= 0:
            continue
        key = (not name.startswith(BENCH_SPAN), cover, -(he - hs))
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce_dir(path: str, n_devices: int) -> Dict:
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    import jax
    pd = jax.profiler.ProfileData.from_file(max(files, key=os.path.getmtime))
    return reduce_profile(pd, n_devices)
