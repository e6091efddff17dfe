"""The program's own marks in a JAX profiler trace (``*.xplane.pb``):
its host spans and the named scopes of its model step.

host_span_s  {span: [seconds inside the window, count]} of the host
             events whose name starts with ``repro.`` (the spans of
             ``CodedTrainer.run``, src/repro/tracing.py);
scope_s      {scope: [device seconds per chip]} of the XLA operations
             whose ``tf_op`` path (the HLO ``op_name``, where
             ``jax.named_scope`` lands) holds the scope as a whole
             ``/``-separated component, read through transform wrappers
             such as ``jvp(..)`` and ``transpose(..)``.

The window is the benchmark's (``bench.xplane``): from the start of the
first ``bench.chunk`` annotation to the end of the last.  An operation's
``tf_op`` sits in its event metadata, which ``jax.profiler.ProfileData``
does not expose: ``op_paths`` reads it from the file's protobuf wire
format with the standard library alone.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from . import xplane

SPAN_PREFIX = "repro."
# the named scopes of the program's step (src/repro/tracing.py SCOPES)
SCOPES = ("attention", "mlp", "head_ce", "optimizer")
# transforms JAX writes around a scope's name: jvp(mlp), transpose(jvp(mlp))
WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")


# ----------------------- protobuf wire format ---------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not supported")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_entry(entry) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for f, v in _fields(entry):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_paths(data: bytes) -> Dict[Tuple[str, str], Optional[str]]:
    """{(plane name, event name): tf_op} of every event metadata that
    carries a ``tf_op`` stat.  XSpace.planes (1); XPlane name (2),
    event_metadata (4), stat_metadata (5); XEventMetadata name (2),
    stats (5); XStat metadata_id (1), str_value (5), ref_value (7);
    XStatMetadata name (2).  A name that two metadata of one plane
    carry with different paths maps to None."""
    out: Dict[Tuple[str, str], Optional[str]] = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = _text(pv)
            elif pf == 4:
                events.append(_map_entry(pv)[1])
            elif pf == 5:
                key, md = _map_entry(pv)
                stat_names[key] = next(
                    (_text(v) for sf, v in _fields(md) if sf == 2), "")
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        if not tf_op:
            continue
        for md in events:
            ev_name, path = "", None
            for ef, ev in _fields(md):
                if ef == 2:
                    ev_name = _text(ev)
                elif ef == 5:
                    stat = dict(_fields(ev))
                    if stat.get(1) in tf_op:
                        path = (_text(stat[5]) if 5 in stat else
                                stat_names.get(stat.get(7)))
            if path is None:
                continue
            key = (name, ev_name)
            out[key] = path if out.get(key, path) == path else None
    return out


# ----------------------------- reduction --------------------------------

def scope_of(tf_op: Optional[str], scopes=SCOPES) -> Optional[str]:
    """The innermost of ``scopes`` that is a whole component of the
    path, transform wrappers read through: 'jit(f)/transpose(jvp(mlp))/dot'
    and 'jit(f)/while/body/mlp/dot' both give 'mlp'; 'jit(mlp)/dot' (a
    jitted function's name) gives None."""
    found = None
    for part in (tf_op or "").split("/"):
        while True:
            m = WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        if part in scopes:
            found = part
    return found


def window(pd) -> Tuple[float, float, list]:
    """(start, end, host events) of the traced window."""
    host = []
    for p in pd.planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                host.extend(xplane._events(line))
    spans = [(s, e) for s, e, n in host if n == xplane.WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {xplane.WINDOW_SPAN!r} annotation in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans), host


def reduce_profile(pd, n_devices: int,
                   paths: Dict[Tuple[str, str], Optional[str]]) -> Dict:
    """``host_span_s`` and ``scope_s`` of a trace; ``paths`` from
    ``op_paths`` of the same file."""
    lo, hi, host = window(pd)
    spans: Dict[str, List] = {}
    for s, e, n in host:
        if n.startswith(SPAN_PREFIX) and e > lo and s < hi:
            acc = spans.setdefault(n, [0.0, 0])
            acc[0] += min(e, hi) - max(s, lo)
            acc[1] += 1
    planes = xplane.device_planes(pd, n_devices)
    scope_s: Dict[str, List[float]] = {}
    for i, plane in enumerate(planes):
        per: Dict[str, list] = defaultdict(list)
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for s, e, text in xplane._events(line):
                if e <= lo or s >= hi or \
                        xplane.op_name(text)[1] in xplane.CONTAINER:
                    continue
                scope = scope_of(paths.get((plane.name, text)))
                if scope is not None:
                    per[scope].append((max(s, lo), min(e, hi)))
        for scope, iv in per.items():
            scope_s.setdefault(scope, [0.0] * len(planes))[i] = \
                xplane.total(xplane.union(iv))
    return {"host_span_s": spans, "scope_s": scope_s}


def reduce_file(path: str, n_devices: int) -> Dict:
    import jax
    with open(path, "rb") as f:
        data = f.read()
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    return reduce_profile(pd, n_devices, op_paths(data))


def reduce_dir(path: str, n_devices: int) -> Dict:
    """The newest trace under ``path``, as ``xplane.reduce_dir`` picks."""
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return reduce_file(max(files, key=os.path.getmtime), n_devices)
