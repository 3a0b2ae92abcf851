"""The readers of what the program stamps on a trace
(perfbench/stamps.py): kernel launches and chain glue of the ``gs_step``
program, device idle inside the program's ``gs.iterate`` / ``gs.sync``
spans, and the program's own runner load time. Made-up traces for the
arithmetic; the committed chip traces for what a real one reads, with
the program's spans as ``trace_reduce.from_profile`` collects them and
the record as ``run.py`` builds it.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness as H  # noqa: E402
from perfbench import stamps  # noqa: E402
from perfbench import trace_reduce as T  # noqa: E402

TESTDATA = os.path.join(ROOT, "perfbench", "testdata")
NEW = ("kernel_launch_us_per_step", "chain_glue_us_per_step",
       "dispatch_idle_us_per_round", "sync_idle_us_per_round",
       "runner_load_s")
STAMP = ', frontend_attributes={kernel_metadata={\n"fuse":"2",\n' \
        '"gs_kernel":"xy_chain"\n}}'
LAUNCH = ("%gs_xy_chain.3 = (f32[4,8]{1,0}, f32[4,8]{1,0}) custom-call("
          "f32[4,8]{1,0} %u, f32[4,8]{1,0} %v), custom_call_target="
          '"tpu_custom_call"')


def _read(metric, trace, record):
    return H.load_reader(metric, ROOT)(trace, record)


def _op(text, s, e, module="jit_gs_step(42)"):
    return T.Op(text, s, e, module)


def _hlo(opcode, name="%x"):
    return f"{name} = f32[4,8]{{1,0}} {opcode}(f32[4,8]{{1,0}} %a)"


def test_metadata_leaves_hlo_bytes_unchanged():
    assert T.hlo_bytes(LAUNCH + STAMP) == T.hlo_bytes(LAUNCH) == 4 * 128
    recorded = ("%_fused_call.9 = (f32[256,256,256]{2,1,0:T(8,128)}, "
                "f32[256,256,256]{2,1,0:T(8,128)}) custom-call(f32[6]{0:"
                "T(128)S(1)} %p, f32[256,256,256]{2,1,0:T(8,128)} %copy.21)"
                ', custom_call_target="tpu_custom_call", frontend_attributes'
                "={kernel_metadata={}}")
    stamped = recorded.replace("kernel_metadata={}", STAMP.split(
        "kernel_metadata=")[1][:-1])
    assert "gs_kernel" in stamped
    assert T.hlo_bytes(stamped) == T.hlo_bytes(recorded)
    assert T.Op(stamped, 0, 1).opcode == "custom-call"


def _two_chips():
    """Two chips, 4 steps: launches 0-6 and 10-16 on each, the step
    program's pads, slices and copies around them, a collective and an
    op of another program that neither reader counts."""
    def chip(shift):
        return [
            _op(_hlo("pad", "%pad.1"), 6 + shift, 8),
            _op(LAUNCH + STAMP, 0, 6),
            _op(LAUNCH + STAMP, 10, 16),
            _op(_hlo("copy", "%copy.2"), 16, 18),
            _op(_hlo("slice", "%slice.3"), 18, 19 + shift),
            _op(_hlo("collective-permute-done", "%cp"), 8, 10),
            _op(_hlo("convert"), 20, 21, module="jit_convert(7)"),
        ]

    return T.Reduced({0: chip(0), 1: chip(1)},
                     [("perfbench.round", 0, 40)])


def test_launch_and_glue_split_the_step_program():
    red = _two_chips()
    rec = {"steps": 4}
    # 12 ns of launches per chip over 4 steps.
    assert _read("kernel_launch_us_per_step", red, rec) == pytest.approx(
        12e-3 / 4)
    # Chip 0: pad 2 + copy 2 + slice 1; chip 1: pad 1 + copy 2 + slice 2.
    assert _read("chain_glue_us_per_step", red, rec) == pytest.approx(
        5e-3 / 4)


def test_launch_and_glue_read_nothing_without_stamps():
    """A trace of a program that does not stamp its work (module
    ``jit__unknown``, empty kernel_metadata) reads None, not 0."""
    plain = LAUNCH + ", frontend_attributes={kernel_metadata={}}"
    red = T.Reduced({0: [_op(plain, 0, 6, "jit__unknown(1)"),
                         _op(_hlo("copy"), 6, 8, "jit__unknown(1)")]},
                    [("perfbench.round", 0, 10)])
    for m in ("kernel_launch_us_per_step", "chain_glue_us_per_step"):
        assert _read(m, red, {"steps": 4}) is None
    # A stamped launch in another program is not the step's.
    red = T.Reduced({0: [_op(LAUNCH + STAMP, 0, 6, "jit_other(1)")]},
                    [("perfbench.round", 0, 10)])
    assert _read("kernel_launch_us_per_step", red, {"steps": 4}) is None


def test_idle_is_split_by_overlap_with_program_spans():
    """One chip idle over [10, 30) and [50, 60) of a [0, 60) window;
    gs.iterate [5, 15) and [45, 55), gs.sync [15, 22): the first gap
    splits 5 (iterate) / 7 (sync) / 8 (outside), the second 5 / 0 / 5."""
    red = T.Reduced({0: [_op(_hlo("fusion"), 0, 10),
                         _op(_hlo("fusion"), 30, 50)]},
                    [("perfbench.round", 0, 60)])
    red.program_spans = [("gs.iterate", 5, 15), ("gs.sync", 15, 22),
                         ("gs.iterate", 45, 55), ("gs.compile", 0, 60)]
    assert _read("dispatch_idle_us_per_round", red, {}) == pytest.approx(
        (5 + 5) * 1e-3 / 2)
    assert _read("sync_idle_us_per_round", red, {}) == pytest.approx(
        7e-3 / 1)


def test_idle_is_averaged_over_chips_and_none_without_spans():
    red = T.Reduced({0: [_op(_hlo("fusion"), 0, 10)],
                     1: [_op(_hlo("fusion"), 0, 4)]},
                    [("perfbench.round", 0, 10)])
    assert _read("dispatch_idle_us_per_round", red, {}) is None
    red.program_spans = [("gs.sync", 2, 12)]  # clipped to the window
    # Chip 0 is never idle, chip 1 idle over [4, 10).
    assert _read("sync_idle_us_per_round", red, {}) == pytest.approx(
        6e-3 / 2)
    assert _read("dispatch_idle_us_per_round", red, {}) is None


def test_runner_load_sums_the_programs_compile_seconds():
    ex = [{"name": "runner[200]", "compile_s": 4.5, "cache": "hit"},
          {"name": "runner[50]", "compile_s": 0.25, "cache": "hit"}]
    assert _read("runner_load_s", None, {"executables": ex}) == 4.75
    assert _read("runner_load_s", None, {}) is None
    assert _read("runner_load_s", None, {"executables": [
        {"name": "runner[200]", "compile_s": None}]}) is None


class _Event:
    def __init__(self, name, s, e):
        self.name, self.start_ns, self.end_ns = name, s, e


class _Line:
    def __init__(self, events):
        self.events = events


class _Plane:
    def __init__(self, name, events):
        self.name, self.lines = name, [_Line(events)]


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_collect_spans_takes_host_gs_events_without_attribute_suffix():
    pd = _Profile([
        _Plane("/host:CPU", [_Event("gs.iterate#step=0,nsteps=200#", 1, 2),
                             _Event("gs.sync", 2, 5),
                             _Event("perfbench.round", 0, 6),
                             _Event("PjitFunction(gs_step)", 1, 2)]),
        _Plane("/device:TPU:0", [_Event("gs.sync", 0, 9)]),
    ])
    assert stamps.collect_spans(pd) == [("gs.iterate", 1, 2),
                                        ("gs.sync", 2, 5)]


@pytest.fixture(scope="module")
def parent_round():
    """The one-round L=256 trace recorded before the program stamped
    its work (perfbench/testdata/gs256-1round)."""
    d = os.path.join(TESTDATA, "gs256-1round")
    with open(os.path.join(d, "record.json"), encoding="utf-8") as f:
        record = json.load(f)
    record["peaks"] = T.peaks("TPU v5 lite")
    return T.load(d, n_devices=1), record


def test_unstamped_trace_reads_none_for_every_new_metric(parent_round):
    red, record = parent_round
    assert red.program_spans == []
    for m in NEW:
        assert _read(m, red, record) is None, m


@pytest.fixture(scope="module")
def stamped_round():
    """One 200-step round of gs256-f32-compute recorded on a TPU v5e
    with the program's stamps (perfbench/testdata/gs256-1round-spans):
    40 ``gs_chain`` launches (fuse 5) in ``jit_gs_step``, one
    ``gs.iterate`` and one ``gs.sync`` span."""
    d = os.path.join(TESTDATA, "gs256-1round-spans")
    with open(os.path.join(d, "record.json"), encoding="utf-8") as f:
        record = json.load(f)
    record["peaks"] = T.peaks("TPU v5 lite")
    return T.load(d, n_devices=1), record


def test_stamped_trace_names_the_step_program_and_its_launches(
        stamped_round):
    red, _ = stamped_round
    launches = [o for o in red.mosaic_ops() if stamps.is_launch(o)]
    assert len(launches) == len(red.mosaic_ops()) == 40
    assert {o.module.split("(")[0] for o in red.ops[0]} == {"jit_gs_step"}
    assert all('"gs_kernel":"chain"' in o.text and '"fuse":"5"' in o.text
               for o in launches)
    # The stamp leaves the launch's byte count as it was.
    assert {o.bytes for o in launches} == {4 * 256**3 * 4}
    names = [n for n, _, _ in red.program_spans]
    assert names.count("gs.iterate") == 1 and names.count("gs.sync") == 1


def test_stamped_trace_reads_every_new_metric(stamped_round):
    red, rec = stamped_round
    got = {m: _read(m, red, rec) for m in NEW}
    assert got == pytest.approx({
        "kernel_launch_us_per_step": 280.65398,
        "chain_glue_us_per_step": 79.12007,
        "dispatch_idle_us_per_round": 652.229,
        "sync_idle_us_per_round": 1169.547,
        "runner_load_s": 3.884333,
    })
    # Launch and glue cover the step program's busy time (>= 97%), the
    # launches are the old kernel metric, and the two idle spans hold
    # no more than the round's idle time.
    busy_us = red.busy_s * 1e6 / rec["steps"]
    assert (got["kernel_launch_us_per_step"]
            + got["chain_glue_us_per_step"]) >= 0.97 * busy_us
    assert got["kernel_launch_us_per_step"] == pytest.approx(
        _read("kernel_us_per_step", red, rec), rel=0.02)
    idle_us = (red.window_s - red.busy_s) * 1e6
    assert (got["dispatch_idle_us_per_round"]
            + got["sync_idle_us_per_round"]) <= idle_us


def test_from_profile_collects_the_programs_spans():
    """The reduced trace carries the ``gs.*`` spans that load_spans
    reads alone from the same file; a trace without them carries none."""
    d = os.path.join(TESTDATA, "gs256-1round-spans")
    red = T.load(d, n_devices=1)
    assert red.program_spans == stamps.load_spans(d)
    assert {n for n, _, _ in red.program_spans} >= {"gs.iterate", "gs.sync"}
    assert T.load(os.path.join(TESTDATA, "gs256-1round"),
                  n_devices=1).program_spans == []


class _TracedRun:
    """What run.py's traced record takes from a cell's run."""

    def __init__(self, rec):
        self.settings = type("S", (), {"L": rec["L"]})()
        self.spans = {"construct": rec["runner_compile_s"],
                      "first_round": 0.0}
        self.info = {"executables": rec["executables"]}

    def extra_record(self):
        return {}


@pytest.mark.parametrize("cell", ["gs256-f32-compute", "gs1024-f32-2x2x1"])
def test_every_listed_metric_reads_from_the_runs_record(cell):
    """Each per-layer metric BENCHMARK.json lists for the cell reads a
    number from the committed stamped trace and the record that run.py
    builds from a run (the three host-dispatch and set-up readers
    among them); exchange_exposed alone needs more than one chip."""
    from perfbench import run as R

    d = os.path.join(TESTDATA, "gs256-1round-spans")
    with open(os.path.join(d, "record.json"), encoding="utf-8") as f:
        rec = json.load(f)
    c = H.load_cell(cell, H.load_benchmark(ROOT), ROOT)
    record = R.traced_record(
        c, _TracedRun(rec), {"steps": rec["steps"], "wall_s": 1.0},
        rec["chips"], "TPU v5 lite")
    assert record["executables"] == rec["executables"]
    red = T.load(d, n_devices=1)
    got = {m["name"]: _read(m["name"], red, record) for m in c["per_layer"]}
    assert {"dispatch_idle_us_per_round", "sync_idle_us_per_round",
            "runner_load_s"} <= set(got)
    for name, value in got.items():
        if name != "exchange_exposed_us_per_step":
            assert value is not None and value > 0, name
    assert got["runner_load_s"] == pytest.approx(3.884333)
