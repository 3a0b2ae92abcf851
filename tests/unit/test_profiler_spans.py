"""The program's spans and names on the JAX profiler's timeline.

Every tracer span (the null tracer's too, with ``GS_TRACE`` unset) is a
``gs.<name>`` TraceAnnotation, so a capture that is open — on the CPU
here — holds the program's host spans in its ``.xplane.pb``; the step
runner's jitted program is named ``gs_step``.
"""

import contextlib
import glob
import os
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from grayscott_jl_tpu.config.settings import Settings
from grayscott_jl_tpu.obs import trace as obs_trace
from grayscott_jl_tpu.obs.trace import NULL_TRACER, SpanTracer
from grayscott_jl_tpu.simulation import Simulation


def _host_events(trace_dir):
    """``[(name, stats)]`` of the capture's host events named ``gs.*``."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("gs."):
                        out.append((ev.name, dict(ev.stats)))
    return out


@pytest.fixture
def capture(tmp_path, monkeypatch):
    """Opens a CPU profiler capture with GS_TRACE unset; yields a
    function that closes it and returns its ``gs.*`` host events."""
    monkeypatch.delenv("GS_TRACE", raising=False)
    obs_trace.reset_tracer()
    d = str(tmp_path / "prof")
    jax.profiler.start_trace(d)
    state = {"open": True}

    def stop():
        jax.profiler.stop_trace()
        state["open"] = False
        return _host_events(d)

    yield stop
    if state["open"]:
        jax.profiler.stop_trace()
    obs_trace.reset_tracer()


def test_tracer_spans_are_profiler_annotations(capture, tmp_path):
    with NULL_TRACER.span("outer", phase="compute", step=3) as attrs:
        attrs["cache"] = "hit"  # an outcome known only at the end
    t = SpanTracer(str(tmp_path / "t.json"))
    with t.span("inner", step=4, runner="runner[8]"):
        pass
    with t.span("late") as attrs:
        attrs["cache"] = "miss"
    ev = dict(capture())
    assert ev["gs.outer"] == {"step": 3, "cache": "hit"}
    assert ev["gs.inner"] == {"step": 4, "runner": "runner[8]"}
    assert ev["gs.late"] == {"cache": "miss"}
    # The Chrome trace records the late attribute too.
    args = {e["name"]: e["args"] for e in t._events}
    assert args["late"] == {"cache": "miss"}


def test_annotation_is_inert_without_jax(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax.profiler")
    assert isinstance(obs_trace.annotation("x", step=1),
                      contextlib.nullcontext)
    with NULL_TRACER.span("x", step=1) as attrs:
        attrs["k"] = 1


def test_round_leaves_iterate_and_sync_spans(capture):
    sim = Simulation(Settings(L=8, noise=0.1, precision="Float32",
                              backend="CPU", kernel_language="Plain"))
    sim.iterate(3)
    sim.block_until_ready()
    ev = capture()
    names = [n for n, _ in ev]
    assert names.count("gs.iterate") == 1 and names.count("gs.sync") == 1
    assert dict(ev)["gs.iterate"] == {"step": 0, "nsteps": 3}
    assert dict(ev)["gs.sync"] == {"step": 3}


def test_step_program_is_named_gs_step():
    sim = Simulation(Settings(L=8, noise=0.1, precision="Float32",
                              backend="CPU", kernel_language="Plain"))
    runner = sim._runner(2)
    args = (*sim.fields, sim.base_key, jnp.int32(sim.step), sim.params)
    text = (runner.lower(*args).as_text() if hasattr(runner, "lower")
            else runner.as_text())
    assert "jit_gs_step" in text.splitlines()[0]
