"""The benchmark harness (perfbench/) on the CPU at tiny sizes.

Each cell's code path runs through the harness's own functions (the
command itself refuses the CPU): a sound run comes out correct; the
control (the program's ``bf16_f32acc`` posture, or Float32 where the
configuration states Float64) and each fault the cell can have, planted
underneath the timed path, come out not correct. Cells of another model
(``heat``) and of another precision (Float64) are added as files under
a copy of ``perfbench/`` alone, and a model with no reference module is
refused as the cell is loaded. Also: BENCHMARK.json keeps the contract's
shape, new cells, configurations and metric readers are found by name
as new files, and ``run.py`` exits non-zero with no result where there
is no TPU.
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
    "heat16": (16, 10, {}),
    "gs16-f64": (16, 10, {}),
}
#: The control where it is not the bf16_f32acc posture: the program one
#: precision below the configuration's, the reference at the stated one.
CONTROL = {"gs16-f64": {"precision": "Float32"}}


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


#: A reference module for the program's ``heat`` model, written from
#: models/heat.py's declaration (one field T, ghost 0, T=1 on the seed
#: cube [L/2-6, L/2+6]^3, ``T_t = D*lap(T) + noise*U(-1, 1)``, D 0.2 by
#: default) with the Gray-Scott reference's stencil and noise stream.
HEAT_REFERENCE = '''
from perfbench.reference.grayscott import laplacian, unit_noise

FIELDS = ("T",)


def params(settings):
    table = dict(settings.model_params or {})
    return {"D": table.get("D", 0.2), "dt": settings.dt,
            "noise": settings.noise}


def init(xp, L, dtype):
    idx = xp.arange(L)
    inside = (idx >= L // 2 - 6) & (idx <= L // 2 + 6)
    cube = (inside[:, None, None] & inside[None, :, None]
            & inside[None, None, :])
    return (xp.where(cube, 1.0, 0.0).astype(dtype),)


def step(xp, t, step_index, params, key, L=None):
    c = {k: xp.asarray(params[k], dtype=t.dtype) for k in params}
    dt_ = c["D"] * laplacian(xp, t, 0.0)
    if params["noise"] != 0.0:
        pm1 = unit_noise(xp, key, step_index, t.shape,
                         row=t.shape[2] if L is None else L)
        dt_ = dt_ + c["noise"] * pm1.astype(t.dtype)
    return ((t + dt_ * c["dt"]).astype(t.dtype),)
'''
_GS = """Du = 0.2
Dv = 0.1
F = 0.02
k = 0.048
dt = 1.0
noise = 0.1
backend = "TPU"
kernel_language = "XLA"
"""
#: Files a later PR would add for a heat cell, a Float64 cell and a cell
#: of a model with no reference (brusselator), and nothing else.
FILES_ONLY = {
    "perfbench/reference/heat.py": HEAT_REFERENCE,
    "perfbench/configs/heat-L16.toml": (
        'L = 16\ndt = 1.0\nnoise = 0.1\nprecision = "Float32"\n'
        'kernel_language = "XLA"\n[model]\nname = "heat"\nD = 0.2\n'),
    # Sound runs read 2.4e-7 to 3.6e-7 over five seeds (float32 rounding:
    # the program folds the stencil's 1/6 into a multiply); the altered
    # answer reads 5.8e-2, the unchanged state 0.89.
    "perfbench/limits/heat16.json": '{"T_max_abs_err": 1e-5}',
    "perfbench/configs/gs-L16-f64.toml": (
        "L = 16\n" + _GS + 'precision = "Float64"\n'),
    # Over five seeds sound Float64 runs read 4.4e-16 (u) and 5.6e-16 to
    # 6.7e-16 (v), rounding in the 16th digit; the program at Float32
    # reads 2.0e-7 to 2.6e-7. The limit lies 1,500 times above the one
    # and 200,000 times below the other.
    "perfbench/limits/gs16-f64.json": (
        '{"u_max_abs_err": 1e-12, "v_max_abs_err": 1e-12}'),
    "perfbench/configs/bruss-L16.toml": (
        'L = 16\nprecision = "Float32"\n[model]\nname = "brusselator"\n'),
    "perfbench/limits/bruss16.json": '{"u_max_abs_err": 1e-5}',
}
FILES_ONLY_ENTRIES = {
    "configs": [{"name": n, "source": "x", "file": f"perfbench/configs/{n}"
                 ".toml", "reduced": [], "why": "x"}
                for n in ("heat-L16", "gs-L16-f64", "bruss-L16")],
    "workloads": [{"name": w, "config": c, "traffic": "rounds200",
                   "chips": 1, "why": "x"}
                  for w, c in (("heat16", "heat-L16"),
                               ("gs16-f64", "gs-L16-f64"),
                               ("bruss16", "bruss-L16"))],
}


def _files_only_tree(tmp_path):
    """A copy of perfbench/ plus FILES_ONLY, and the BENCHMARK.json that
    names them: (root, bench)."""
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench")
    for rel, text in FILES_ONLY.items():
        (tmp_path / rel).write_text(text)
    b = _bench()
    for key, entries in FILES_ONLY_ENTRIES.items():
        b[key] = b[key] + entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return str(tmp_path), H.load_benchmark(str(tmp_path))


def _bench():
    return H.load_benchmark(ROOT)


def _bench_with_staged():
    b = _bench()
    for key, entries in STAGED.items():
        b[key] = b[key] + entries
    return b


def _run(name, patch=None, root=ROOT, bench=None, control=None):
    """One run of the cell ``name`` at its tiny size, through set-up,
    window and comparison, with ``control``'s settings for the program
    alone; returns (correct, checks)."""
    cell = H.load_cell(name, bench or _bench_with_staged(), root)
    L, rounds, over = TINY[name]
    s = H.build_settings(cell, backend="CPU")
    s.L = L
    for k, v in dict(over, **(control or {})).items():
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
    # Added as files only (FILES_ONLY).
    ("heat16", "sound"), ("heat16", "unchanged"), ("heat16", "altered"),
    ("gs16-f64", "sound"), ("gs16-f64", "control"),
    ("bruss16", "refused"),
]


@pytest.mark.parametrize("name,case", CASES,
                         ids=[f"{n}-{c}" for n, c in CASES])
def test_correct_separates_sound_runs_from_control_and_faults(
        monkeypatch, tmp_path, name, case):
    root, bench = ROOT, None
    if any(w["name"] == name for w in FILES_ONLY_ENTRIES["workloads"]):
        root, bench = _files_only_tree(tmp_path)
    if case == "refused":
        missing = os.path.join(root, "perfbench", "reference",
                               "brusselator.py")
        with pytest.raises(FileNotFoundError, match=re.escape(missing)):
            H.load_cell(name, bench, root)
        return
    patch = control = None
    if case == "control" and name in CONTROL:
        control = CONTROL[name]
    elif case == "control":
        monkeypatch.setenv("GS_COMPUTE_PRECISION", "bf16_f32acc")
    elif case == "unchanged":
        _unchanged(monkeypatch)
    elif case == "half":
        patch = lambda L: _half_left_out(monkeypatch, L)  # noqa: E731
    elif case == "altered":
        patch = lambda L: _answer_altered(monkeypatch, L)  # noqa: E731
    elif case == "exchange":
        _exchange_left_out(monkeypatch)
    correct, checks = _run(name, patch, root, bench, control)
    assert correct == (case == "sound"), checks
