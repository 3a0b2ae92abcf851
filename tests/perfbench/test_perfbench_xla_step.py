"""The readers of the XLA step program (``xla_step_roofline``,
``xla_step_bytes_per_update``) on made-up traces of Float64
``jit_gs_step`` ops with known shapes and times, on a round of
``gs256-f64-xla`` recorded on the chip, and on the committed stamped
trace of the Pallas cell, where they read nothing."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness as H  # noqa: E402
from perfbench import trace_reduce as T  # noqa: E402

METRICS = ("xla_step_roofline", "xla_step_bytes_per_update")
L = 4
#: One Float64 step at L=4 as XLA runs it: pad u and v by the boundary
#: (512 B read, 1,728 B written each; the f64[] pad value is 8 B), then
#: one fusion that reads both padded fields and writes u and v.
STEP = (
    "%pad.1 = f64[6,6,6]{2,1,0} pad(f64[4,4,4]{2,1,0} %u, f64[] %one), "
    "padding=1_1x1_1x1_1",
    "%pad.2 = f64[6,6,6]{2,1,0} pad(f64[4,4,4]{2,1,0} %v, f64[] %zero), "
    "padding=1_1x1_1x1_1",
    "%fusion.3 = (f64[4,4,4]{2,1,0}, f64[4,4,4]{2,1,0}) fusion("
    "f64[6,6,6]{2,1,0} %pad.1, f64[6,6,6]{2,1,0} %pad.2, s32[] %step), "
    "kind=kLoop, calls=%fused_computation",
)
STEP_BYTES = 2 * (512 + 1728 + 8) + (2 * 512 + 2 * 1728 + 4)
LAUNCH = ('%gs_chain.9 = (f64[4,4,4]{2,1,0}, f64[4,4,4]{2,1,0}) custom-call('
          'f64[4,4,4]{2,1,0} %u, f64[4,4,4]{2,1,0} %v), custom_call_target='
          '"tpu_custom_call", frontend_attributes={kernel_metadata={\n'
          '"fuse":"5",\n"gs_kernel":"chain"\n}}')


def _read(metric, trace, record):
    return H.load_reader(metric, ROOT)(trace, record)


def _record(steps=2):
    return {"L": L, "steps": steps, "chips": 1, "flops_per_cell_update": 30.0,
            "peaks": T.peaks("TPU v5 lite")}


def _steps(n, module="jit_gs_step(7)", t0=0):
    """``n`` steps of STEP, each op 10 ns long, back to back."""
    return [T.Op(text, t0 + 10 * i, t0 + 10 * i + 10, module)
            for i, text in enumerate(STEP * n)]


def _trace(ops, hi=1000):
    return T.Reduced({0: ops}, [("perfbench.round", 0, hi)])


def test_bytes_per_update_counts_the_step_programs_hlo_shapes():
    assert sum(T.Op(t, 0, 1).bytes for t in STEP) == STEP_BYTES == 8980
    got = _read("xla_step_bytes_per_update", _trace(_steps(2)), _record())
    assert got == pytest.approx(2 * STEP_BYTES / (L**3 * 2))
    # Above the Float64 floor of one pass: u and v read and written once.
    assert got > 32


def test_roofline_is_the_hbm_bound_over_the_step_ops_time():
    rec = _record()
    got = _read("xla_step_roofline", _trace(_steps(2)), rec)
    hbm_s = 2 * STEP_BYTES / rec["peaks"]["hbm_bytes_per_s"]
    flops_s = 30.0 * L**3 * 2 / rec["peaks"]["bf16_flops_per_s"]
    assert hbm_s > flops_s
    assert got == pytest.approx(100.0 * hbm_s / 60e-9)


def test_other_programs_and_collectives_are_left_out():
    ops = (_steps(2)
           + _steps(3, module="jit_convert(1)", t0=100)
           + [T.Op("%cp = f64[4,4,4]{2,1,0} collective-permute-done("
                   "f64[4,4,4]{2,1,0} %x)", 200, 260, "jit_gs_step(7)")])
    trace, rec = _trace(ops), _record()
    base = _trace(_steps(2))
    for m in METRICS:
        assert _read(m, trace, rec) == pytest.approx(_read(m, base, rec))


def test_nothing_to_read_without_the_xla_step_program():
    rec = _record()
    for m in METRICS:
        # No step program.
        assert _read(m, _trace(_steps(2, module="jit_other(1)")), rec) is None
        # A Mosaic launch in the step program: the Pallas paths.
        with_launch = _steps(2) + [T.Op(LAUNCH, 60, 90, "jit_gs_step(7)")]
        assert _read(m, _trace(with_launch), rec) is None
        # No step in the window.
        assert _read(m, _trace(_steps(2)), _record(steps=0)) is None


#: An asynchronous prefetch of a float32 half into on-chip memory (S(1))
#: and an eviction back to HBM, as v5e's Float64 step runs them.
_HALF = "f32[4,4,4]{2,1,0:T(8,128)}"
_ONCHIP = "f32[4,4,4]{2,1,0:T(8,128)S(1)}"
_CTX = "u32[]{:S(2)}"
PREFETCH = (f"%copy-start = ({_ONCHIP}, {_HALF}, {_CTX}) copy-start("
            f"{_HALF} %a)",
            f"%copy-done = {_ONCHIP} copy-done(({_ONCHIP}, {_HALF}, {_CTX}) "
            "%copy-start)")
EVICT = (f"%copy-start.1 = ({_HALF}, {_ONCHIP}, {_CTX}) copy-start("
         f"{_ONCHIP} %b)",
         f"%copy-done.1 = {_HALF} copy-done(({_HALF}, {_ONCHIP}, {_CTX}) "
         "%copy-start.1)")


@pytest.mark.parametrize("pair", [PREFETCH, EVICT], ids=["prefetch", "evict"])
def test_an_async_copy_moves_its_hbm_end_once(pair):
    from perfbench.metrics.xla_step_bytes_per_update import op_bytes

    start, done = (T.Op(t, 0, 1) for t in pair)
    assert (start.opcode, done.opcode) == ("copy-start", "copy-done")
    # One 256 B half moves between HBM and on-chip memory: its HBM end.
    assert (op_bytes(start), op_bytes(done)) == (256, 0)
    assert start.bytes + done.bytes == 3 * 256  # hlo_bytes alone


@pytest.fixture(scope="module")
def f64_round():
    """One 200-step round of gs256-f64-xla recorded on a TPU v5e
    (perfbench/testdata/gs256-f64-1round): the Float64 XLA step as nine
    fusions over float32 halves, plain and async copies, and the
    program's spans."""
    d = os.path.join(ROOT, "perfbench", "testdata", "gs256-f64-1round")
    with open(os.path.join(d, "record.json"), encoding="utf-8") as f:
        rec = json.load(f)
    rec["peaks"] = T.peaks("TPU v5 lite")
    return T.load(d, n_devices=1), rec


def test_f64_round_reads_the_xla_step(f64_round):
    from perfbench import stamps

    red, rec = f64_round
    assert {o.module.split("(")[0] for o in red.ops[0]} == {"jit_gs_step"}
    assert rec["executables"][0]["tpu_custom_calls"] == 0
    assert {n for n, _, _ in red.program_spans} == {"gs.iterate", "gs.sync"}
    ops = stamps.step_ops(red)[0]
    assert sum(o.opcode == "fusion" and o.bytes >= 4 * 256**3
               for o in ops) == 9 * 200
    got = {m: _read(m, red, rec) for m in METRICS}
    assert got == pytest.approx({"xla_step_bytes_per_update": 264.30001,
                                 "xla_step_roofline": 61.18336})
    assert got["xla_step_bytes_per_update"] >= 32
    assert 0 < got["xla_step_roofline"] < 100


def test_f64_round_reads_the_cells_listed_metrics(f64_round):
    """Every per-layer metric BENCHMARK.json lists for gs256-f64-xla
    reads a number from the recorded round; the readers of stamped
    Mosaic launches read nothing there."""
    red, rec = f64_round
    cell = H.load_cell("gs256-f64-xla", H.load_benchmark(ROOT), ROOT)
    for m in cell["per_layer"]:
        value = _read(m["name"], red, rec)
        assert value is not None and value > 0, m["name"]
    for m in ("stencil_roofline", "kernel_launch_us_per_step",
              "chain_glue_us_per_step"):
        assert _read(m, red, rec) is None, m


def test_pallas_cells_trace_reads_nothing():
    """The committed stamped round of gs256-f32-compute: its step
    program is the Mosaic ``gs_chain`` kernel, which stencil_roofline
    reads."""
    d = os.path.join(ROOT, "perfbench", "testdata", "gs256-1round-spans")
    with open(os.path.join(d, "record.json"), encoding="utf-8") as f:
        rec = json.load(f)
    rec["peaks"] = T.peaks("TPU v5 lite")
    red = T.load(d, n_devices=1)
    for m in METRICS:
        assert _read(m, red, rec) is None
    assert _read("stencil_roofline", red, rec) is not None
