"""The reduction of the program's own marks in a trace
(bench/program_trace.py): host spans named ``repro.*`` and the named
scopes in the XLA operations' ``tf_op`` paths; and the reduction that
bench/xplane.py makes of the committed chip traces, pinned."""

from pathlib import Path

import pytest

from bench import harness, program_trace, xplane

ROOT = Path(harness.ROOT)
ONE_CHIP = ROOT / "bench" / "data" / "one_chip.xplane.pb"
CODED_STEP = ROOT / "bench" / "data" / "coded_step.xplane.pb"


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


FUS = "%fusion.{} = f32[8]{{0}} fusion(f32[8] %p)"


def _chip(i, ops):
    return _Plane(f"/device:TPU:{i}", [
        _Line("XLA Modules", [_Ev("jit_step_fn(3)", 0.0, 10.0)]),
        _Line("XLA Ops", [_Ev(n, s, d) for n, s, d in ops])])


def test_scope_of_reads_through_transform_wrappers():
    s = program_trace.scope_of
    assert s("jit(step_fn)/jvp()/while/body/closed_call/attention/"
             "dot_general") == "attention"
    assert s("jit(step_fn)/transpose(jvp(head_ce))/dot_general") == \
        "head_ce"
    assert s("jit(step_fn)/transpose(jvp(head_ce))/jvp(head_ce)/"
             "checkpoint/rematted_computation/dot_general") == "head_ce"
    assert s("jit(step_fn)/optimizer/sub") == "optimizer"
    # a scope's name inside another component, or a jitted function's
    # name, is no scope
    assert s("jit(step_fn)/jvp()/mlp_in/dot_general") is None
    assert s("jit(mlp)/dot_general") is None
    assert s("jit(step_fn)/vmap(mlp)/dot_general") == "mlp"
    assert s("jit(step_fn)/jvp()/convert_element_type") is None
    assert s(None) is None


def test_reduction_of_a_described_two_chip_trace():
    """A 10 s window (two chunks, 1-11 s); host spans before, across and
    inside it; on chip 0 ops under four scopes, one outside any scope,
    one loop op around them, one op crossing the window's end; chip 1
    runs one attention op."""
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("bench.chunk", 1.0, 5.0), _Ev("bench.chunk", 6.0, 5.0),
        _Ev("repro.train.decode", 0.2, 0.5),      # before the window
        _Ev("repro.train.batch", 0.5, 1.0),       # 0.5 s inside
        _Ev("repro.train.batch", 6.0, 0.25),
        _Ev("repro.train.h2d", 6.25, 0.125),
        _Ev("repro.train.dispatch", 6.375, 0.0625),
        _Ev("repro.train.log", 10.5, 1.0),        # 0.5 s inside
        _Ev("bench.setup.warmup", 0.0, 1.0),      # not the program's
        _Ev("PjitFunction(step_fn)", 6.4, 0.01)])])
    ops0 = [("%while.1 = (s32[]) while(s32[] %a)", 1.0, 4.0),
            (FUS.format(1), 1.0, 1.0), (FUS.format(2), 2.0, 1.5),
            (FUS.format(3), 3.5, 0.5), (FUS.format(4), 4.0, 1.0),
            (FUS.format(5), 5.0, 0.5),
            (FUS.format(6), 10.5, 1.0)]            # 0.5 s inside
    chip0 = _chip(0, ops0)
    chip1 = _chip(1, [(FUS.format(1), 1.0, 2.0)])
    step = "jit(step_fn)"
    paths = {
        ("/device:TPU:0", ops0[0][0]): f"{step}/jvp()/while",
        ("/device:TPU:0", FUS.format(1)):
            f"{step}/jvp()/while/body/closed_call/attention/dot_general",
        ("/device:TPU:0", FUS.format(2)):
            f"{step}/transpose(jvp())/while/body/closed_call/checkpoint/"
            "mlp/dot_general",
        ("/device:TPU:0", FUS.format(3)):
            f"{step}/transpose(jvp(head_ce))/dot_general",
        ("/device:TPU:0", FUS.format(4)): f"{step}/optimizer/sub",
        ("/device:TPU:0", FUS.format(5)):
            f"{step}/jvp()/convert_element_type",
        ("/device:TPU:0", FUS.format(6)): f"{step}/optimizer/mul",
        ("/device:TPU:1", FUS.format(1)):
            f"{step}/jvp(attention)/dot_general",
    }
    pd = _Profile([host, chip0, chip1])
    red = program_trace.reduce_profile(pd, 2, paths)
    spans = red["host_span_s"]
    assert set(spans) == {"repro.train.batch", "repro.train.h2d",
                          "repro.train.dispatch", "repro.train.log"}
    assert spans["repro.train.batch"] == [pytest.approx(0.75), 2]
    assert spans["repro.train.h2d"] == [pytest.approx(0.125), 1]
    assert spans["repro.train.dispatch"] == [pytest.approx(0.0625), 1]
    assert spans["repro.train.log"] == [pytest.approx(0.5), 1]
    sc = red["scope_s"]
    assert set(sc) == {"attention", "mlp", "head_ce", "optimizer"}
    assert sc["attention"] == pytest.approx([1.0, 2.0])
    assert sc["mlp"] == pytest.approx([1.5, 0.0])
    assert sc["head_ce"] == pytest.approx([0.5, 0.0])
    assert sc["optimizer"] == pytest.approx([1.5, 0.0])   # 1.0 + 0.5 clipped
    # the same trace through the benchmark's own reduction is unchanged
    # by the program's marks: the idle gap is named by a program span
    base = xplane.reduce_profile(pd, 2)
    assert base["busy_per_device_s"] == pytest.approx([5.0, 2.0])
    name, gap = base["breakdown"]["idle_gaps"][0]     # 5.5-10.5 s
    assert (name, gap) == ("repro.train.batch", pytest.approx(5.0))


def _xspace(ops_text: str) -> bytes:
    import jax
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        ops_text)


XSPACE = """
planes {
  id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.chunk" } }
  event_metadata { key: 2 value { id: 2 name: "repro.train.batch" } }
}
planes {
  id: 2 name: "/device:TPU:0"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 3500000 duration_ps: 250000 }
    events { metadata_id: 4 offset_ps: 3750000 duration_ps: 125000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8] %a)"
    display_name: "fusion.1"
    stats { metadata_id: 6 int64_value: 3 }
    stats { metadata_id: 7 str_value: "jit(step_fn)/transpose(jvp(mlp))/dot_general" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8]{0} fusion(f32[8] %b)"
    stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8]{0} copy(f32[8] %c)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = f32[8]{0} fusion(f32[8] %d)"
    stats { metadata_id: 7 str_value: "jit(step_fn)/jvp(attention)/add" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.4 = f32[8]{0} fusion(f32[8] %d)"
    stats { metadata_id: 7 str_value: "jit(other)/mlp/add" } } }
  stat_metadata { key: 6 value { id: 6 name: "flops" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 9 value { id: 9 name: "jit(step_fn)/optimizer/sub" } }
}
"""


def test_tf_op_is_read_from_the_files_event_metadata(tmp_path):
    """The wire-format reader: a path as a string and as a reference to
    a stat metadata's name; an op with no path; one name that two
    metadata give different paths is left unattributed."""
    data = _xspace(XSPACE)
    paths = program_trace.op_paths(data)
    tpu = "/device:TPU:0"
    assert paths[(tpu, "%fusion.1 = f32[8]{0} fusion(f32[8] %a)")] == \
        "jit(step_fn)/transpose(jvp(mlp))/dot_general"
    assert paths[(tpu, "%fusion.2 = f32[8]{0} fusion(f32[8] %b)")] == \
        "jit(step_fn)/optimizer/sub"
    assert (tpu, "%copy.3 = f32[8]{0} copy(f32[8] %c)") not in paths
    assert paths[(tpu, "%fusion.4 = f32[8]{0} fusion(f32[8] %d)")] is None
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(data)
    red = program_trace.reduce_dir(str(tmp_path), n_devices=1)
    assert red["host_span_s"] == {
        "repro.train.batch": [pytest.approx(1e-6), 1]}
    assert red["scope_s"] == {"mlp": [pytest.approx(1e-6)],
                              "optimizer": [pytest.approx(5e-7)]}


# bench/xplane.py's reduction of each committed chip trace, as computed
# before the program carried any marks (one_chip) and when the coded step
# was recorded (coded_step)
PINNED = {
    "one_chip": {
        "window_s": 0.013357409,
        "busy_s": 0.00015546100000000257,
        "busy_per_device_s": [0.00015546100000000257],
        "collective_s": [0], "collective_exposed_s": [0],
        "module_s": {"local": [0.00015549999999999592]},
        "breakdown": {
            "device_ops": [["fusion.9", 7.360099999999953e-05],
                           ["fusion.15", 7.104200000000033e-05],
                           ["reduce.1", 1.0770999999999697e-05],
                           ["copy-start", 3.9000000000288804e-08],
                           ["copy-done", 8.000000002728047e-09]],
            "idle_gaps": [["$time sleep", 0.004425287],
                          ["$time sleep", 0.0033422830000000014],
                          ["$time sleep", 0.0031635609999999953],
                          ["$time sleep", 0.0022708019999999954],
                          ["$time sleep", 2.0000000058861822e-09],
                          ["$time sleep", 2.0000000058861822e-09],
                          ["$time sleep", 1.9999999989472883e-09],
                          ["$time sleep", 1.9999999989472883e-09],
                          ["$time sleep", 9.999999994736442e-10],
                          ["$time sleep", 9.999999994736442e-10]]}},
    "coded_step": {
        "window_s": 0.011813609000000003,
        "busy_s": 0.00013106299999993826,
        "busy_per_device_s": [0.00013106299999993826],
        "collective_s": [0], "collective_exposed_s": [0],
        "module_s": {"step_fn": [0.0001466800000000032]},
        "breakdown": {
            "device_ops": [
                ["fusion.1", 6.087999999994376e-06],
                ["fusion.2", 4.284000000007171e-06],
                ["convert_bitcast_fusion.7 fusion", 3.0050000000073074e-06],
                ["fusion.522", 2.8120000000048107e-06],
                ["convert_bitcast_fusion.6 fusion", 2.6649999999919793e-06],
                ["fusion.511", 2.2490000000097154e-06],
                ["fusion.536", 2.2469999999968904e-06],
                ["fusion.516", 2.184000000009234e-06],
                ["fusion.510", 2.18199999998947e-06],
                ["fusion.535", 2.1720000000016726e-06]],
            "idle_gaps": [["repro.train.log", 0.004626906],
                          ["repro.train.batch", 0.004299001999999996],
                          ["repro.train.batch", 0.0027472470000000013],
                          ["Transpose::Execute", 4.02499999999778e-06],
                          ["Linearize", 3.965000000001606e-06],
                          ["Linearize", 3.9699999999920355e-07],
                          ["Transpose::Execute", 3.520000000020729e-07],
                          ["Transpose::Execute", 6.200000000206041e-08],
                          ["Linearize", 6.200000000206041e-08],
                          ["Transpose::Execute", 2.9999999984209325e-09]]}},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_reduction_of_the_committed_traces_is_pinned(name):
    import jax
    path = ROOT / "bench" / "data" / f"{name}.xplane.pb"
    pd = jax.profiler.ProfileData.from_file(str(path))
    assert xplane.reduce_profile(pd, n_devices=1) == PINNED[name]


def test_the_one_chip_trace_carries_no_program_marks():
    red = program_trace.reduce_file(str(ONE_CHIP), n_devices=1)
    assert red == {"host_span_s": {}, "scope_s": {}}


def test_reduction_of_the_coded_step_recorded_on_the_chip():
    """Two steps of the coded trainer at small widths on one chip
    (bench/record_coded_step.py): each step's four host spans, the last
    step's log, and all four scopes of the step reduce to nonzero
    times."""
    assert CODED_STEP.stat().st_size < 300_000
    red = program_trace.reduce_file(str(CODED_STEP), n_devices=1)
    spans = red["host_span_s"]
    assert set(spans) == {"repro.train.decode", "repro.train.batch",
                          "repro.train.h2d", "repro.train.dispatch",
                          "repro.train.log"}
    for name, (secs, count) in spans.items():
        assert secs > 0
        assert count == (1 if name == "repro.train.log" else 2)
    assert set(red["scope_s"]) == set(program_trace.SCOPES)
    assert all(len(v) == 1 and v[0] > 0 for v in red["scope_s"].values())
    # the scopes cover most of the step's device time; the rest is the
    # parameter cast, the embedding and the scan's slicing
    step = PINNED["coded_step"]["module_s"]["step_fn"][0]
    scoped = sum(v[0] for v in red["scope_s"].values())
    assert 0.5 * step < scoped < step


def test_the_recorder_drops_only_the_named_plane():
    import jax
    from bench import record_coded_step
    data = _xspace(XSPACE)
    out = record_coded_step.without_plane(data, "/host:CPU")
    assert [p.name for p in
            jax.profiler.ProfileData.from_serialized_xspace(out).planes] == \
        ["/device:TPU:0"]
    assert program_trace.op_paths(out) == program_trace.op_paths(data)
    assert record_coded_step.without_plane(data, "/host:none") == data
