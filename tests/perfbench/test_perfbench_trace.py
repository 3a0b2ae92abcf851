"""Trace reduction (perfbench/trace_reduce.py) and the per-layer metric
readers, on the CPU: interval arithmetic and HLO byte counts on made-up
inputs, and every metric against traces recorded on the chip
(perfbench/testdata). ``ProfileData`` is jaxlib's reader; nothing here
loads libtpu.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness as H  # noqa: E402
from perfbench import trace_reduce as T  # noqa: E402

TESTDATA = os.path.join(ROOT, "perfbench", "testdata")


def test_interval_arithmetic():
    u = T.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert T.total(u) == 7
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert T.clip([(0, 4), (6, 8)], 2, 7) == [(2, 4), (6, 7)]


def test_hlo_bytes_of_in_place_and_slicing_ops():
    """On the chip, XLA updates a field in place and slices the halo
    rows off (gs1024-f32-2x2x1's runner): their shapes overstate the
    bytes moved."""
    dus = ("%dynamic_update_slice.116 = f32[512,512,1024]{2,1,0:T(8,128)} "
           "dynamic-update-slice(f32[512,512,1024]{2,1,0:T(8,128)} "
           "%slice.3071, f32[512,2,1024]{2,1,0:T(2,128)S(1)} %gte.918, "
           "s32[]{:T(128)} %c, s32[]{:T(128)} %c, s32[]{:T(128)} %c)")
    assert T.hlo_bytes(dus) == 2 * 512 * 2 * 1024 * 4
    sl = ("%slice.3071 = f32[512,512,1024]{2,1,0:T(8,128)} slice("
          "f32[512,520,1024]{2,1,0:T(8,128)} %gte.888), "
          "slice={[0:512], [2:514], [0:1024]}")
    assert T.hlo_bytes(sl) == 2 * 512 * 512 * 1024 * 4


def test_hlo_bytes_counts_each_array_once():
    text = ("%custom-call.1 = (f32[256,256,256]{2,1,0}, "
            "f32[256,256,256]{2,1,0}) custom-call(f32[256,256,256]{2,1,0} "
            "%p.0, f32[256,256,256]{2,1,0} %p.1, s32[3]{0} %seeds, "
            "f32[256,256,256]{2,1,0} %p.0), custom_call_target="
            "\"tpu_custom_call\"")
    assert T.hlo_bytes(text) == 4 * 256**3 * 4 + 12
    assert T.hlo_bytes("") == 0


def test_unknown_device_kind_is_an_error():
    assert T.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        T.peaks("cpu")


@pytest.fixture(scope="module")
def one_round():
    """One 200-step round of gs256-f32-compute, traced on the chip (my
    chip run, PR 22): 40 launches of the fused Mosaic kernel (fuse=5),
    each reading u, v and writing u, v, f32[256,256,256]."""
    d = os.path.join(TESTDATA, "gs256-1round")
    with open(os.path.join(d, "record.json"), encoding="utf-8") as f:
        record = json.load(f)
    record["peaks"] = T.peaks("TPU v5 lite")
    return T.load(d, n_devices=1), record


def _read(metric, trace, record):
    return H.load_reader(metric, ROOT)(trace, record)


def test_recorded_trace_reduces(one_round):
    red, _ = one_round
    ops = red.kernel_ops()
    calls = [o for o in ops if o.opcode == "custom-call"]
    assert len(calls) == 40
    assert {o.bytes for o in calls} == {4 * 256**3 * 4}
    # The loop's field copies are not kernel time.
    assert not [o for o in ops if o.kind != "compute"]
    assert red.window_s == pytest.approx(0.073820158)
    assert 0 < red.busy_s < red.window_s
    b = red.breakdown()
    assert b["device_ops"][0][0] == "%_fused_call.9"
    assert b["idle_gaps"][0][0] == "perfbench.round"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_metrics_read_known_values(one_round):
    red, rec = one_round
    kernel_s = 0.056135176  # sum of the step program's compute ops
    assert _read("kernel_us_per_step", red, rec) == pytest.approx(
        kernel_s * 1e6 / 200)
    # HBM bound: 40 x 268435456 B at 819 GB/s over the 40 Mosaic
    # launches' time; the flops term is ~10^3 times smaller.
    least = 40 * 4 * 256**3 * 4 / 819e9
    assert _read("stencil_roofline", red, rec) == pytest.approx(
        100 * least / 0.056131397)
    assert _read("device_idle_share", red, rec) == pytest.approx(
        100 * (1 - 0.071446274 / 0.073820158))
    assert _read("runner_compile_s", red, rec) == 6.5
    # Nothing to read: one chip has no exchange, the cell no output.
    assert _read("exchange_exposed_us_per_step", red, rec) is None
    assert _read("output_exposed_ms_per_snapshot", red, rec) is None


def test_output_exposed_reads_runstats_io():
    io = {"steps_written": 100, "exposed_s": {"device_to_host": 0.012164,
                                              "output": 0.555768}}
    got = _read("output_exposed_ms_per_snapshot", None,
                {"io_runs": [io, io]})
    assert got == pytest.approx(1e3 * 2 * 0.567932 / 200)


def test_exposed_exchange_is_collective_time_with_nothing_else_running():
    """Per chip: collective op time not covered by any other op; the
    metric takes the chip where it is largest, per step."""
    def op(kind, s, e):
        return T.Op(f"%x = f32[1]{{0}} {kind}(f32[1]{{0}} %a)", s, e, "m")

    red = T.Reduced({
        0: [op("fusion", 0, 10), op("collective-permute-done", 8, 14)],
        1: [op("fusion", 0, 5), op("collective-permute-done", 6, 7)],
    }, [("perfbench.round", 0, 20)])
    assert red.exposed_collective_ns(0) == 4
    assert red.exposed_collective_ns(1) == 1
    got = _read("exchange_exposed_us_per_step", red, {"steps": 2})
    assert got == pytest.approx(4e-3 / 2)
    assert red.busy_s == pytest.approx((14 + 6) * 1e-9 / 2)
