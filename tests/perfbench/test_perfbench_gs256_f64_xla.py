"""The ``gs256-f64-xla`` cell's own files (BENCHMARK.json,
``configs/gs-L256-f64.toml``, ``limits/gs256-f64-xla.json``) through the
harness on the CPU, at L=16 with 10-step rounds: the sound Float64 run
passes the cell's limits, and the program at Float32 against the same
Float64 reference (the control), an unchanged state and an altered
answer fail them."""

import os
import sys

import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness as H  # noqa: E402

CELL = "gs256-f64-xla"
SEED = 2_147_500_026


def _cell():
    return H.load_cell(CELL, H.load_benchmark(ROOT), ROOT)


def test_cell_states_float64_on_the_xla_step():
    cell = _cell()
    s = H.build_settings(cell)
    assert (s.L, s.precision, s.kernel_language, s.noise) == (
        256, "Float64", "XLA", 0.1)
    assert cell["reference_dtype"] == "float64"
    assert cell["reference"].FIELDS == ("u", "v")
    assert cell["traffic_spec"] == {"entry": "simulation",
                                    "round_steps": 200}
    assert cell["chips"] == 1
    assert {m["name"] for m in cell["per_layer"]} >= {
        "xla_step_roofline", "xla_step_bytes_per_update"}
    # The Pallas-only readers are not listed for this cell.
    assert not {m["name"] for m in cell["per_layer"]} & {
        "stencil_roofline", "kernel_launch_us_per_step",
        "chain_glue_us_per_step"}


def _unchanged(monkeypatch):
    from grayscott_jl_tpu.simulation import Simulation

    def iterate(self, nsteps=1):
        self.step += nsteps

    monkeypatch.setattr(Simulation, "iterate", iterate)


def _altered(monkeypatch):
    """One cell of u altered where each round produces it."""
    from grayscott_jl_tpu.simulation import Simulation

    orig = Simulation.iterate

    def iterate(self, nsteps=1):
        orig(self, nsteps)
        u, *rest = self.fields
        self.fields = (u.at[5, 8, 4].add(1e-6), *rest)

    monkeypatch.setattr(Simulation, "iterate", iterate)


CASES = ["sound", "control", "unchanged", "altered"]


@pytest.mark.parametrize("case", CASES)
def test_cell_limits_separate_sound_float64_from_control_and_faults(
        monkeypatch, case):
    cell = _cell()
    s = H.build_settings(cell, backend="CPU")
    s.L = 16
    if case == "control":
        s.precision = "Float32"
    elif case == "unchanged":
        _unchanged(monkeypatch)
    elif case == "altered":
        _altered(monkeypatch)
    cell["traffic_spec"]["round_steps"] = 10
    run = H.make_cell(cell, s, SEED, cell["chips"])
    run.setup()
    assert run.info["kernel_language"] == "xla"
    assert {str(f.dtype) for f in run.compared} == (
        {"float32"} if case == "control" else {"float64"})
    win = run.window(0.0)
    assert win["attempted"] == 1 and win["steps"] == 10
    run.free()
    correct, judged = H.judge(run.check(jax.devices()[:1]), cell["limits"])
    assert correct == (case == "sound"), judged
    if case == "control":
        # Float32 rounding fails the limit in both fields.
        assert all(c["value"] > c["limit"] for c in judged.values()), judged
