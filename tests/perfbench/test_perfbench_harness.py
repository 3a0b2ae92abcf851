"""The benchmark harness (perfbench/) on the CPU at tiny sizes.

Each cell's code path runs through the harness's own functions (the
command itself refuses the CPU): a sound run comes out correct; the
control (the program's ``bf16_f32acc`` posture) and each fault the cell
can have, planted underneath the timed path, come out not correct.
Also: BENCHMARK.json keeps the contract's shape, new cells,
configurations and metric readers are found by name as new files, and
``run.py`` exits non-zero with no result where there is no TPU.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import harness as H  # noqa: E402

SEED = 2_500_000_017
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
#: Tiny stand-ins for each cell: (L, round steps, overrides).
TINY = {
    "gs256-f32-compute": (16, 10, {}),
    # On the chip L=64 runs the XLA kernel (the Mosaic gate): so here.
    "gs64-tutorial": (16, None, {"steps": 300, "plotgap": 30,
                                 "kernel_language": "Plain"}),
    # Long enough for the control to pass the cell's limit (2e-2).
    "gs1024-f32-2x2x1": (16, 100, {}),
}


#: The upstream tutorial cell, staged as files under perfbench/ but not
#: in BENCHMARK.json (PERF.md, Open questions): its entries as a later
#: PR would add them.
STAGED = {
    "configs": [{"name": "gs-upstream-L64", "source": "x",
                 "file": "perfbench/configs/gs-upstream-L64.toml",
                 "reduced": ["backend", "kernel_language"], "why": "x"}],
    "workloads": [{"name": "gs64-tutorial", "config": "gs-upstream-L64",
                   "traffic": "upstream-runs", "chips": 1, "why": "x"}],
}


def _bench():
    return H.load_benchmark(ROOT)


def _bench_with_staged():
    b = _bench()
    for key, entries in STAGED.items():
        b[key] = b[key] + entries
    return b


def _run(name, patch=None):
    """One run of the cell ``name`` at its tiny size, through set-up,
    window and comparison; returns (correct, checks)."""
    cell = H.load_cell(name, _bench_with_staged())
    L, rounds, over = TINY[name]
    s = H.build_settings(cell, backend="CPU")
    s.L = L
    for k, v in over.items():
        setattr(s, k, v)
    if rounds:
        cell["traffic_spec"]["round_steps"] = rounds
    if patch:
        patch(L)
    run = H.make_cell(cell, s, SEED, cell["chips"])
    run.setup()
    win = run.window(0.0)
    assert win["attempted"] == 1 and win["steps"] > 0
    run.free()
    checks = run.check(jax.devices()[: cell["chips"]])
    return H.judge(checks, cell["limits"])


# ----------------------------------------------------------- the contract


def test_benchmark_json_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "perfbench/run.py"]
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= b["run_seconds"] <= 51
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in b["workloads"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 2)
    names = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in names
        names.add((w["config"], w["traffic"]))
        H.load_cell(w["name"], b, ROOT)  # traffic and limits files exist
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(
            ROOT, "perfbench", "metrics", m["name"] + ".py"))
        assert m["layer"] and "\n" not in m["layer"]
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m


def test_new_cell_config_and_metric_are_found_by_name(tmp_path):
    """A later PR adds files and entries and edits nothing: the harness
    finds the new cell's configuration, traffic and limits, and the new
    reader, by the names in BENCHMARK.json."""
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench")
    b = _bench()
    b["configs"].append({"name": "gs-new", "source": "x",
                         "file": "perfbench/configs/gs-new.toml",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "gs-new.cell", "config": "gs-new",
                           "traffic": "new-mix", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "new_metric", "unit": "s",
                           "better": "lower", "source": "host_clock",
                           "layer": "kernel", "moves": "setup_s",
                           "workloads": ["gs-new.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    pb = tmp_path / "perfbench"
    (pb / "configs" / "gs-new.toml").write_text(
        "L = 32\nprecision = \"Float32\"\n[bench]\nflops_per_cell_update = 30\n")
    (pb / "traffic" / "new-mix.toml").write_text(
        "entry = \"simulation\"\nround_steps = 7\n")
    (pb / "limits" / "gs-new.cell.json").write_text(
        '{"u_max_abs_err": 1.0}')
    (pb / "metrics" / "new_metric.py").write_text(
        "def read(trace, record):\n    return record['x'] * 2\n")
    cell = H.load_cell("gs-new.cell", H.load_benchmark(str(tmp_path)),
                       str(tmp_path))
    assert cell["traffic_spec"]["round_steps"] == 7
    assert H.build_settings(cell).L == 32
    assert [m["name"] for m in cell["per_layer"]] == [
        "kernel_us_per_step", "device_idle_share", "runner_compile_s",
        "new_metric"]
    assert H.load_reader("new_metric", str(tmp_path))(None, {"x": 3}) == 6


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "gs256-f32-compute", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "needs 1 TPU" in p.stderr


def test_judge():
    ok, out = H.judge({"a": 1e-6, "b": float("inf")},
                      {"a": 1e-5, "b": 1.0})
    assert not ok and out["a"] == {"value": 1e-6, "limit": 1e-5}
    assert H.judge({"a": 1e-6}, {"a": 1e-5})[0]
    assert not H.judge({}, {"a": 1e-5})[0]


# ------------------------------------------------- sound, control, faults


def _unchanged(monkeypatch):
    from grayscott_jl_tpu.simulation import Simulation

    def iterate(self, nsteps=1):
        self.step += nsteps

    monkeypatch.setattr(Simulation, "iterate", iterate)


def _wrap_iterate(monkeypatch, after):
    from grayscott_jl_tpu.simulation import Simulation

    orig = Simulation.iterate

    def iterate(self, nsteps=1):
        before = tuple(jnp.copy(f) for f in self.fields)
        orig(self, nsteps)
        self.fields = after(before, tuple(self.fields))

    monkeypatch.setattr(Simulation, "iterate", iterate)


def _half_left_out(monkeypatch, L):
    """Half of the grid is not advanced."""
    _wrap_iterate(monkeypatch, lambda b, a: tuple(
        f.at[: L // 2].set(k[: L // 2]) for k, f in zip(b, a)))


def _answer_altered(monkeypatch, L):
    """One cell of the field altered where the round produces it."""
    _wrap_iterate(monkeypatch, lambda b, a: (
        a[0].at[L // 3, L // 2, L // 4].add(0.05),) + a[1:])


def _exchange_left_out(monkeypatch):
    """Every ppermute returns the sender's own slab: no exchange."""
    monkeypatch.setattr(jax.lax, "ppermute", lambda x, *a, **k: x)


CASES = [
    ("gs256-f32-compute", "sound"), ("gs256-f32-compute", "control"),
    ("gs256-f32-compute", "unchanged"), ("gs256-f32-compute", "half"),
    ("gs256-f32-compute", "altered"),
    ("gs64-tutorial", "sound"), ("gs64-tutorial", "control"),
    ("gs64-tutorial", "unchanged"), ("gs64-tutorial", "half"),
    ("gs64-tutorial", "altered"),
    ("gs1024-f32-2x2x1", "sound"), ("gs1024-f32-2x2x1", "control"),
    ("gs1024-f32-2x2x1", "unchanged"), ("gs1024-f32-2x2x1", "half"),
    ("gs1024-f32-2x2x1", "altered"), ("gs1024-f32-2x2x1", "exchange"),
]


@pytest.mark.parametrize("name,case", CASES,
                         ids=[f"{n}-{c}" for n, c in CASES])
def test_correct_separates_sound_runs_from_control_and_faults(
        monkeypatch, name, case):
    patch = None
    if case == "control":
        monkeypatch.setenv("GS_COMPUTE_PRECISION", "bf16_f32acc")
    elif case == "unchanged":
        _unchanged(monkeypatch)
    elif case == "half":
        patch = lambda L: _half_left_out(monkeypatch, L)  # noqa: E731
    elif case == "altered":
        patch = lambda L: _answer_altered(monkeypatch, L)  # noqa: E731
    elif case == "exchange":
        _exchange_left_out(monkeypatch)
    correct, checks = _run(name, patch)
    assert correct == (case == "sound"), checks
