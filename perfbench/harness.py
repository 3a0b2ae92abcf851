"""The benchmark's harness: cells, set-up, window, comparison.

Everything cell-specific is data found by name (``BENCHMARK.json`` at the
root, then ``configs/<config>.toml``, ``traffic/<traffic>.toml``,
``limits/<cell>.json``, ``metrics/<metric>.py`` and
``reference/<model>.py`` here, the last by the configuration's
``model``), so a later PR adds a cell, a configuration, a model's
reference or a per-layer metric as new files. The
functions take no notice of the platform; ``run.py`` refuses anything
but a TPU before it calls them.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

try:
    import tomllib as _toml
except ImportError:  # pragma: no cover
    import tomli as _toml  # type: ignore


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ data


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


#: The configuration's stated precision -> the reference's dtype. A
#: narrower one (BFloat16) needs a wider reference chosen on purpose.
REFERENCE_DTYPES = {"Float32": "float32", "Float64": "float64"}


def _load_file(path: str, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(table: dict, root: str = ROOT):
    """``reference/<model>.py`` for the configuration ``table`` (its
    ``model`` name or ``[model]`` table's ``name``, as the program
    reads them; "grayscott" where it names none), and the dtype of its
    stated ``precision`` (the program's default, Float64, where it
    states none)."""
    model = table.get("model", "grayscott")
    if isinstance(model, dict):
        model = model.get("name", "grayscott")
    model = str(model).lower()
    path = os.path.join(root, "perfbench", "reference", f"{model}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"model {model!r} has no plain reference: {path} is missing")
    precision = table.get("precision", "Float64")
    if precision not in REFERENCE_DTYPES:
        raise ValueError(
            f"precision {precision!r}: the reference runs only "
            f"{sorted(REFERENCE_DTYPES)}")
    return (_load_file(path, f"perfbench_reference_{model}"),
            REFERENCE_DTYPES[precision])


def load_cell(name: str, bench: dict, root: str = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic, limits and
    reference."""
    here = os.path.join(root, "perfbench")
    cell = dict(_by_name(bench["workloads"], name, "workload"))
    conf = _by_name(bench["configs"], cell["config"], "config")
    with open(os.path.join(root, conf["file"]), encoding="utf-8") as f:
        cell["config_text"] = f.read()
    cell["config_table"] = _toml.loads(cell["config_text"])
    cell["reference"], cell["reference_dtype"] = load_reference(
        cell["config_table"], root)
    with open(os.path.join(here, "traffic", f"{cell['traffic']}.toml"),
              "rb") as f:
        cell["traffic_spec"] = _toml.load(f)
    if cell["traffic_spec"].get("entry") not in CELL_TYPES:
        raise ValueError(f"traffic {cell['traffic']!r}: entry must be one "
                         f"of {sorted(CELL_TYPES)}")
    with open(os.path.join(here, "limits", f"{name}.json"),
              encoding="utf-8") as f:
        cell["limits"] = json.load(f)
    cell["end_to_end"] = [m["name"] for m in bench["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def build_settings(cell: dict, backend=None):
    """The program's own ``Settings`` from the configuration's TOML
    (the ``[bench]`` table is not a settings key and is ignored)."""
    from grayscott_jl_tpu.config.settings import parse_settings_toml

    settings = parse_settings_toml(cell["config_text"])
    if backend is not None:
        settings.backend = backend
    return settings


def load_reader(metric: str, root: str = ROOT):
    """``metrics/<metric>.py``'s ``read(trace, record)``."""
    path = os.path.join(root, "perfbench", "metrics", f"{metric}.py")
    return _load_file(path, f"perfbench_metric_{metric.replace('.', '_')}"
                      ).read


# ---------------------------------------------------------------- spans


@contextlib.contextmanager
def span(name: str, spans: dict):
    """Host span: a ``TraceAnnotation`` in the profiler's trace (the
    idle-gap attribution reads it) plus wall seconds in ``spans``."""
    import jax

    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"perfbench.{name}"):
        yield
    spans[name] = spans.get(name, 0.0) + time.perf_counter() - t


# ----------------------------------------------------------- comparison


def _max_abs_diff(a, b):
    import jax.numpy as jnp

    wide = jnp.promote_types(a.dtype, b.dtype)
    d = jnp.abs(a.astype(wide) - b.astype(wide))
    return jnp.where(jnp.all(jnp.isfinite(a)), jnp.max(d), jnp.inf)


def widest_gaps(got, want, sharding) -> list:
    """Widest gap per field between host arrays ``got`` and the device
    arrays ``want``, computed on the device in the wider of the two
    dtypes (``inf`` where ``got`` is not finite)."""
    import jax

    fn = jax.jit(_max_abs_diff)
    return [float(fn(jax.device_put(g, sharding), w))
            for g, w in zip(got, want)]


def reference(cell, settings, seed, devices, mesh_shape=(1, 1, 1)):
    """The cell's reference, started at step 0: its model's module with
    the settings' parameters, in the configuration's precision."""
    from perfbench.reference.device import Reference

    mod = cell["reference"]
    ref = Reference(mod, settings.L, mod.params(settings), seed, devices,
                    mesh_shape, cell["reference_dtype"])
    ref.start()
    return ref


# ---------------------------------------------------------------- cells


class SimulationCell:
    """Traffic ``entry = "simulation"``: back-to-back
    ``Simulation.iterate(round_steps)`` + ``block_until_ready()``, the
    driver's compute phase, with no output."""

    def __init__(self, cell, settings, seed, chips):
        self.cell, self.settings, self.seed, self.chips = (
            cell, settings, seed, chips)
        self.R = int(cell["traffic_spec"]["round_steps"])
        self.spans: dict = {}
        self.sim = None
        self.compared = None

    def setup(self):
        from grayscott_jl_tpu.simulation import Simulation

        with span("construct", self.spans):
            self.sim = Simulation(self.settings, n_devices=self.chips,
                                  seed=self.seed)
        with span("first_round", self.spans):
            self.sim.iterate(self.R)
            self.sim.block_until_ready()
        self.info = describe(self.sim)
        # The compared round: the window's own runner, from step R, so
        # the absolute step offsets of a later round are covered too.
        with span("compared_round", self.spans):
            self.sim.iterate(self.R)
            self.sim.block_until_ready()
        with span("copy_out", self.spans):
            self.compared = self.sim.get_fields()
        self.compared_step = self.sim.step

    def window(self, seconds):
        rounds = 0
        t0 = time.perf_counter()
        while True:
            with span("round", self.spans):
                self.sim.iterate(self.R)
                self.sim.block_until_ready()
            rounds += 1
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        return {"steps": rounds * self.R, "wall_s": wall,
                "attempted": rounds, "failed": 0}

    def free(self):
        self.sim.fields = ()
        self.sim = None

    def check(self, devices):
        """Widest gap per field, over every cell, between the compared
        round's fields and the reference at the same step."""
        ref = reference(self.cell, self.settings, self.seed, devices,
                        tuple(self.info["mesh_dims"]))
        ref.advance(self.compared_step)
        errs = widest_gaps(self.compared, ref.fields, ref.sharding)
        return {f"{n}_max_abs_err": e
                for n, e in zip(self.cell["reference"].FIELDS, errs)}

    def extra_record(self):
        return {}


class DriverCell:
    """Traffic ``entry = "driver"``: back-to-back whole runs of the
    configuration through ``driver.run_once`` (output and drain
    included), each from the initial fields on the warm Simulation."""

    def __init__(self, cell, settings, seed, chips):
        self.cell, self.settings, self.seed, self.chips = (
            cell, settings, seed, chips)
        self.spans: dict = {}
        self.sim = None
        self.tmp = tempfile.mkdtemp(prefix="perfbench-")
        self.settings.output = os.path.join(self.tmp, "out.bp")
        self.io_runs: list = []
        self.stats_path = None

    def _factory(self, settings, n_devices=None, seed=0):
        self.sim.restore_fields(self.initial, 0)
        return self.sim

    def _run_once(self):
        from grayscott_jl_tpu import driver

        # Each run writes a fresh store (and .vtk series) in the same place.
        for name in os.listdir(self.tmp):
            shutil.rmtree(os.path.join(self.tmp, name), ignore_errors=True)
        driver.run_once(self.settings, n_devices=self.chips,
                        seed=self.seed, sim_factory=self._factory)
        if self.stats_path and os.path.exists(self.stats_path):
            with open(self.stats_path, encoding="utf-8") as f:
                self.io_runs.append(json.load(f).get("io") or {})

    def setup(self):
        from grayscott_jl_tpu.simulation import Simulation

        with span("construct", self.spans):
            self.sim = Simulation(self.settings, n_devices=self.chips,
                                  seed=self.seed)
            self.initial = tuple(np.asarray(f) for f in self.sim.fields)
        with span("first_round", self.spans):
            self.sim.iterate(self.settings.plotgap)
            self.sim.block_until_ready()
        # One whole run warms every other program the window uses: the
        # snapshot copy and the store path.
        with span("warm_run", self.spans):
            self._run_once()
        self.info = describe(self.sim)
        # The store's variables, as io/stream.py names them.
        self.store_names = [n.upper() for n in self.sim.model.field_names]
        self.io_runs.clear()

    def window(self, seconds):
        runs = 0
        t0 = time.perf_counter()
        while True:
            with span("run_once", self.spans):
                self._run_once()
            runs += 1
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        return {"steps": runs * self.settings.steps, "wall_s": wall,
                "attempted": runs, "failed": 0}

    def free(self):
        self.sim.fields = ()
        self.sim = None

    def check(self, devices):
        """Widest gap per field between every snapshot of the window's
        last store, read back with the store reader, and the reference
        at the same step."""
        from grayscott_jl_tpu.io.bplite import BpReader

        s = self.settings
        names = self.cell["reference"].FIELDS
        ref = reference(self.cell, s, self.seed, devices[:1])
        errs = [0.0] * len(names)
        reader = BpReader(s.output)
        try:
            nsteps = reader.num_steps()
            want_steps = s.steps // s.plotgap
            if (nsteps, len(self.store_names)) != (want_steps, len(names)):
                log(f"store holds {nsteps} steps of {self.store_names}, "
                    f"expected {want_steps} of {len(names)} fields")
                return {f"{n}_max_abs_err": float("inf") for n in names}
            for i in range(nsteps):
                ref.advance(s.plotgap)
                want = [np.asarray(f) for f in ref.fields]
                for fi, name in enumerate(self.store_names):
                    got = reader.get(name, step=i)
                    d = np.abs(got.astype(np.float64) - want[fi])
                    e = float(np.max(d)) if np.all(np.isfinite(got)) \
                        else float("inf")
                    errs[fi] = max(errs[fi], e)
        finally:
            reader.close()
            shutil.rmtree(self.tmp, ignore_errors=True)
        return {f"{n}_max_abs_err": e for n, e in zip(names, errs)}

    def extra_record(self):
        return {"io_runs": list(self.io_runs)}


CELL_TYPES = {"simulation": SimulationCell, "driver": DriverCell}


def describe(sim) -> dict:
    """What ran: kernel language, mesh, and the compile seconds and
    persistent-cache outcome of each runner (the program's own
    ``obs/xstats`` records)."""
    return {
        "kernel_language": sim.kernel_language,
        "mesh_dims": list(sim.domain.dims),
        "executables": [
            {k: r.get(k) for k in ("name", "compile_s", "cache",
                                   "tpu_custom_calls")}
            for r in getattr(sim, "executables", []) or []
        ],
    }


def make_cell(cell, settings, seed, chips):
    return CELL_TYPES[cell["traffic_spec"]["entry"]](cell, settings, seed,
                                                     chips)


def judge(checks: dict, limits: dict):
    """``correct`` and the numbers compared, each beside its limit."""
    out = {}
    ok = True
    for name, limit in limits.items():
        value = checks.get(name)
        passed = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(passed)
        out[name] = {"value": value, "limit": limit}
    return ok, out
