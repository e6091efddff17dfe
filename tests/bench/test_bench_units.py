"""The benchmark's yardstick pieces: operation counts, the peak table,
the trace reduction, and the data-driven layout of cells, configurations,
traffic mixes and metric readers."""

import json
import re
import shutil
from pathlib import Path

import pytest

from bench import flops, harness, xplane

ROOT = Path(harness.ROOT)
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
TRACE = ROOT / "bench" / "data" / "one_chip.xplane.pb"


def config(name):
    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    return json.loads((ROOT / entry["file"]).read_text())["model"]


@pytest.mark.parametrize("name,params", [("minicpm-2b", 527.3e6),
                                         ("starcoder2-7b", 670.1e6)])
def test_parameter_count_matches_the_published_cut(name, params):
    assert abs(flops.param_count(config(name)) - params) < 0.05e6


def test_flops_per_token_by_hand():
    m = config("minicpm-2b")
    # per layer: q, k, v, o (4 d^2 for MHA) + SwiGLU (3 d f); head V d
    d, f, V = 2304, 5760, 122753
    per_layer = 4 * d * d + 3 * d * f
    matmul = 4 * per_layer + V * d
    assert flops.matmul_params(m) == matmul
    attn = 6 * 4 * (36 * 64) * (256 + 1)
    assert flops.train_flops_per_token(m, 256) == 6 * matmul + attn
    s = config("starcoder2-7b")
    # GQA: k and v are 4 heads of 128; GeLU MLP has two matrices
    d, f, V = 4608, 18432, 49152
    assert flops.matmul_params(s) == d * d * 2 + 2 * d * 512 + 2 * d * f \
        + V * d


def test_the_peak_table_knows_v5e_and_refuses_an_unknown_kind():
    assert flops.peak("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peak("cpu")


def test_interval_arithmetic():
    u = xplane.union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)]
    assert xplane.total(u) == 4
    assert xplane.subtract([(0, 10)], [(1, 2), (4, 6)]) == \
        [(0, 1), (2, 4), (6, 10)]
    assert xplane.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]
    # a collective half hidden behind compute is half exposed
    coll, other = [(0.0, 4.0)], [(0.0, 2.0)]
    assert xplane.total(xplane.subtract(coll, other)) == 2.0
    assert xplane.module_key("jit_step_fn(17)") == "step_fn"


def test_reduction_of_a_trace_recorded_on_the_chip():
    import jax
    pd = jax.profiler.ProfileData.from_file(str(TRACE))
    red = xplane.reduce_profile(pd, n_devices=1)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert len(red["busy_per_device_s"]) == 1
    assert red["breakdown"]["device_ops"]
    assert all(t > 0 for _, t in red["breakdown"]["device_ops"])
    assert len(red["breakdown"]["device_ops"]) <= 10
    assert len(red["breakdown"]["idle_gaps"]) <= 10
    assert red["module_s"]


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200
        spec = harness.load_cell(w["name"])
        assert set(spec["limits"]) >= {"loss", "grad", "update"}
        assert spec["traffic"]["data"]["seed"] == \
            spec["traffic"]["code"]["seed"], \
            "the program draws its code graph and its data from one seed"


def test_a_new_cell_needs_only_new_files(tmp_path):
    """Adding a cell adds a traffic file, a limits file and entries in
    BENCHMARK.json; no existing file of the harness changes."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*.py")}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / "coded-frc8.json").read_text())
    traffic["trainer"]["seq_len"] = 128
    (root / "bench" / "traffic" / "coded-frc8-seq128.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "limits" / "minicpm-2b.coded-frc8-seq128.json"
     ).write_text(json.dumps({"loss": 1e-3, "grad": 1e-2, "update": 1e-2}))
    bench["workloads"].append({"name": "minicpm-2b.coded-frc8-seq128",
                               "config": "minicpm-2b",
                               "traffic": "coded-frc8-seq128", "chips": 1,
                               "why": "shorter rows"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for d in ("configs",):
        shutil.copytree(ROOT / "bench" / d, root / "bench" / d,
                        dirs_exist_ok=True)
    import importlib.util
    spec_ = importlib.util.spec_from_file_location(
        "bench_copy_harness", root / "bench" / "harness.py")
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    spec = mod.load_cell("minicpm-2b.coded-frc8-seq128", root=root)
    assert spec["traffic"]["trainer"]["seq_len"] == 128
    assert {m["name"] for m in spec["per_layer"]} == \
        {m["name"] for m in BENCH["per_layer"]
         if "workloads" not in m}
    assert {p: p.read_bytes() for p in (root / "bench").rglob("*.py")} == \
        before


class _Ev:
    def __init__(self, name, start_s, dur_s):
        self.name = name
        self.start_ns = start_s * 1e9
        self.duration_ns = dur_s * 1e9


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def _chip(i, ops, modules):
    return _Plane(f"/device:TPU:{i}", [
        _Line("XLA Modules", [_Ev(n, s, d) for n, s, d in modules]),
        _Line("XLA Ops", [_Ev(n, s, d) for n, s, d in ops])])


def test_reduction_of_a_described_two_chip_trace():
    """Two chips over a 10 s window: chip 0 runs a loop over 0-4 s with a
    fusion inside it, a fusion over 5-8 s and an all-reduce over 3.5-6 s;
    chip 1 computes 0-2 s only."""
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.chunk", 0.0, 5.0), _Ev("bench.chunk", 5.0, 5.0),
        _Ev("PjitFunction(step_fn)", 8.2, 0.5)])])
    chip0 = _chip(0, [("%while.1 = (s32[]) while(s32[] %a)", 0.0, 4.0),
                      ("%fusion.1 = f32[8]{0} fusion(f32[8] %b)", 0.5, 3.0),
                      ("%fusion.2 = (f32[8]{0}) fusion(f32[8] %c)", 5.0, 3.0),
                      ("%all-reduce.3 = f32[8]{0} all-reduce(f32[8] %d)",
                       3.5, 2.5)],
                  [("jit_step_fn(3)", 0.0, 4.0), ("jit_step_fn(3)", 5.0, 3.0)])
    chip1 = _chip(1, [("%fusion.1 = f32[8]{0} fusion(f32[8] %b)", 0.0, 2.0)],
                  [("jit_step_fn(3)", 0.0, 2.0)])
    red = xplane.reduce_profile(_Profile([host, chip0, chip1,
                                          _chip(2, [], [])]), n_devices=2)
    assert red["window_s"] == pytest.approx(10.0)
    assert red["busy_per_device_s"] == pytest.approx([8.0, 2.0])
    assert red["busy_s"] == pytest.approx(5.0)
    assert red["collective_s"] == pytest.approx([2.5, 0.0])
    # the all-reduce over 3.5-6 s: the loop op around 0-4 s is no other
    # work, its fusion ends at 3.5 s and the next starts at 5 s: 1.5 s
    assert red["collective_exposed_s"] == pytest.approx([1.5, 0.0])
    assert red["module_s"]["step_fn"] == pytest.approx([7.0, 2.0])
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0][1] == pytest.approx(2.0)       # chip 0 idle 8-10 s
    assert gaps[0][0] == "PjitFunction(step_fn)"
    ops = dict(red["breakdown"]["device_ops"])
    assert "while.1 while" not in ops and "while.1" not in ops
    assert ops["fusion.2"] == pytest.approx(1.5)  # 3 s over two chips
    assert ops["all-reduce.3"] == pytest.approx(1.25)
