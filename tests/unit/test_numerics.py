"""Unit: the device-side flight recorder (``obs/numerics.py`` +
``obs/xstats.py``).

Host-side contracts first (resolver validation, report aggregation,
bounded drift math, the DriftGate precision-policy seam, the zero-
allocation off path — all jax-free, like test_obs), then the in-graph
pieces against a real Simulation: the fused snapshot probe matches
numpy ground truth, arming it leaves the trajectory bitwise untouched,
and the instrumented AOT compile captures cost/memory/collective
analytics plus the persistent-cache hit/miss without changing results.
"""

import json
import tracemalloc

import numpy as np
import pytest

from grayscott_jl_tpu.obs import numerics as obs_numerics
from grayscott_jl_tpu.obs import xstats as obs_xstats
from grayscott_jl_tpu.obs.numerics import (
    NULL_NUMERICS,
    NumericsRecorder,
    NumericsReport,
    resolve_report,
)
from grayscott_jl_tpu.resilience.health import DriftGate

# ------------------------------------------------------------ resolvers


def test_resolve_numerics_env_wins(monkeypatch):
    class S:
        numerics = "boundary"

    monkeypatch.delenv("GS_NUMERICS", raising=False)
    assert obs_numerics.resolve_numerics() == "off"
    assert obs_numerics.resolve_numerics(S()) == "boundary"
    monkeypatch.setenv("GS_NUMERICS", "every_round")
    assert obs_numerics.resolve_numerics(S()) == "every_round"
    monkeypatch.setenv("GS_NUMERICS", "nope")
    with pytest.raises(ValueError):
        obs_numerics.resolve_numerics()


def test_resolve_window(monkeypatch):
    monkeypatch.delenv("GS_NUMERICS_WINDOW", raising=False)
    assert obs_numerics.resolve_window() == 8
    monkeypatch.setenv("GS_NUMERICS_WINDOW", "3")
    assert obs_numerics.resolve_window() == 3
    monkeypatch.setenv("GS_NUMERICS_WINDOW", "0")
    with pytest.raises(ValueError):
        obs_numerics.resolve_window()


def test_resolve_xstats(monkeypatch):
    class S:
        xstats = "on"

    monkeypatch.delenv("GS_XSTATS", raising=False)
    assert obs_xstats.resolve_xstats() is False
    assert obs_xstats.resolve_xstats(S()) is True
    monkeypatch.setenv("GS_XSTATS", "0")
    assert obs_xstats.resolve_xstats(S()) is False
    monkeypatch.setenv("GS_XSTATS", "banana")
    with pytest.raises(ValueError):
        obs_xstats.resolve_xstats()


# -------------------------------------------------------------- reports


def test_resolve_report_layout():
    raw = [1.0, 2.0, 1.5, 10.0, 0,    # u
           -3.0, 4.0, 0.5, 20.0, 2]   # v
    rep = resolve_report(raw, ("u", "v"))
    assert rep.fields["u"] == {"min": 1.0, "max": 2.0, "mean": 1.5,
                               "l2": 10.0, "nonfinite": 0}
    assert rep.fields["v"]["nonfinite"] == 2
    assert rep.finite is False


def test_aggregate_members_math():
    m0 = {"u": {"min": 0.0, "max": 1.0, "mean": 0.5, "l2": 3.0,
                "nonfinite": 0}}
    m1 = {"u": {"min": -1.0, "max": 0.5, "mean": 0.1, "l2": 4.0,
                "nonfinite": 1}}
    rep = NumericsReport.aggregate_members([m0, m1])
    agg = rep.fields["u"]
    assert agg["min"] == -1.0 and agg["max"] == 1.0
    assert agg["mean"] == pytest.approx(0.3)
    assert agg["l2"] == pytest.approx(5.0)  # sqrt(9 + 16)
    assert agg["nonfinite"] == 1
    assert rep.members == [m0, m1]
    assert rep.describe()["members"] == [m0, m1]


# ---------------------------------------------------------------- drift


def _report(**stats):
    base = {"min": 0.0, "max": 1.0, "mean": 0.5, "l2": 10.0,
            "nonfinite": 0}
    base.update(stats)
    return NumericsReport({"u": base})


def test_drift_is_bounded_relative_change():
    rec = NumericsRecorder(("u",), window=4)
    rec.observe(0, _report(mean=1.0))
    assert rec.max_drift == {}  # no reference yet
    rec.observe(1, _report(mean=2.0))  # doubled vs ref 1.0
    assert rec.max_drift["u.mean"] == pytest.approx(0.5)
    # near-zero reference cannot explode the signal: |drift| <= 2
    rec2 = NumericsRecorder(("u",), window=4)
    rec2.observe(0, _report(min=1e-12))
    rec2.observe(1, _report(min=5.0))
    assert rec2.max_drift["u.min"] == pytest.approx(1.0, abs=1e-6)


def test_drift_window_is_trailing_reference():
    rec = NumericsRecorder(("u",), window=2)
    for step, v in enumerate((10.0, 10.0, 10.0, 20.0)):
        rec.observe(step, _report(l2=v))
    # last probe judged against mean(10, 10) -> (20-10)/20 = 0.5
    assert rec.max_drift["u.l2"] == pytest.approx(0.5)


def test_recorder_emits_numerics_and_drift_events(tmp_path):
    from grayscott_jl_tpu.obs.events import EventStream, parse_events

    es = EventStream(str(tmp_path / "e.jsonl"), proc=0)
    rec = NumericsRecorder(
        ("u",), events=es, gate=DriftGate("warn", 0.25), window=4,
    )
    rec.observe(5, _report(mean=1.0), boundary=True)
    rec.observe(10, _report(mean=2.0), boundary=True)
    evs = parse_events(str(tmp_path / "e.jsonl"))
    kinds = [e["kind"] for e in evs]
    assert kinds == ["numerics", "numerics", "drift"]
    assert evs[0]["phase"] == "io" and evs[0]["step"] == 5
    assert evs[0]["attrs"]["fields"]["u"]["mean"] == 1.0
    drift = evs[2]["attrs"]
    assert drift["policy"] == "warn" and drift["limit"] == 0.25
    assert drift["tripped"]["u.mean"] == pytest.approx(0.5)
    assert rec.drift_trips == 1
    d = rec.describe()
    assert d["probes"] == 2 and d["last"]["fields"]["u"]["mean"] == 2.0


def test_recorder_mirrors_gauges():
    from grayscott_jl_tpu.obs.metrics import MetricsRegistry

    m = MetricsRegistry(path="x", enabled=True)
    rec = NumericsRecorder(("u",), metrics=m, labels={"model": "gs"})
    rec.observe(0, _report(mean=1.0))
    rec.observe(1, _report(mean=2.0))
    snap = m.snapshot()
    names = {(g["name"], tuple(sorted(g["labels"].items())))
             for g in snap["gauges"]}
    assert ("numerics_mean",
            (("field", "u"), ("model", "gs"))) in names
    assert any(g["name"] == "numerics_drift" and
               g["labels"]["stat"] == "mean" for g in snap["gauges"])


def test_numerics_off_is_noop_with_zero_allocations():
    """The PR-8 hot-path contract, extended: the off-mode recorder is
    one shared object whose observe allocates nothing."""
    assert NULL_NUMERICS.enabled is False
    assert NULL_NUMERICS.describe() is None
    for _ in range(10):
        NULL_NUMERICS.observe(0, None)
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(10000):
        NULL_NUMERICS.observe(0, None)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "lineno")
                if s.size_diff > 0)
    assert grown < 50_000, f"numerics-off hot path allocated {grown}B"


# ------------------------------------------------------------ DriftGate


def test_drift_gate_policies(monkeypatch):
    gate = DriftGate("warn", 0.5)
    assert gate.check(1, {"u.mean": 0.1}) is None
    ev = gate.check(2, {"u.mean": 0.7, "u.l2": -0.6, "v.max": 0.2})
    assert ev["tripped"] == {"u.mean": 0.7, "u.l2": -0.6}
    assert DriftGate("off", 0.5).check(2, {"u.mean": 0.9}) is None
    # abort/rollback are real policies now (docs/PRECISION.md): the
    # gate raises DriftError — classified through the health taxonomy.
    from grayscott_jl_tpu.resilience.health import DriftError

    g_abort = DriftGate("abort", 0.5)
    ev_a = g_abort.check(3, {"u.mean": 0.9})
    with pytest.raises(DriftError):
        g_abort.enforce(3, ev_a)
    assert not DriftGate("warn", 0.5).raising
    assert DriftGate("rollback", 0.5).raising
    with pytest.raises(ValueError):
        DriftGate("demote", 0.5)  # unknown policies stay loud
    with pytest.raises(ValueError):
        DriftGate("warn", 0.0)
    monkeypatch.setenv("GS_DRIFT_POLICY", "off")
    monkeypatch.setenv("GS_DRIFT_LIMIT", "0.25")
    g = DriftGate.from_env()
    assert g.policy == "off" and g.limit == 0.25


# --------------------------------------------------------------- xstats


def test_collective_counts():
    hlo = """
    %x = collective-permute-start(...)
    %y = collective-permute-done(...)
    %z = all-reduce(...)
    """
    counts = obs_xstats.collective_counts(hlo)
    assert counts == {"collective-permute": 2, "all-reduce": 1}
    assert obs_xstats.collective_counts("add mul") == {}


#: A step runner's HLO in miniature: two field parameters, a face, and
#: every kind of copy the census must tell apart.
_COPY_HLO = """\
HloModule jit_gs_step, input_output_alias={ {0}: (0, {}, may-alias) }

%body (p: (s32[], f32[64,128,128], f32[64,128,128])) -> (s32[], f32[64,128,128], f32[64,128,128]) {
  %copy.21 = f32[64,128,128]{2,1,0:T(8,128)} copy(f32[64,128,128]{2,1,0:T(8,128)} %gte.1), metadata={op_name="copy(x)"}
  %copy-start = (f32[64,128,128]{2,1,0:T(8,128)}, f32[64,128,128]{2,1,0:T(8,128)S(1)}, u32[]{:S(2)}) copy-start(%gte.2)
  %copy-done = f32[64,128,128]{2,1,0:T(8,128)} copy-done(%copy-start)
  %copy.3 = f32[2,128,128]{2,1,0:T(8,128)} copy(%face)
  %copy.4 = f32[]{:T(128)} copy(%params_dt.1)
  %copy.5 = bf16[64,128,128]{2,1,0:T(8,128)(2,1)} copy(%cast)
  %fusion.9 = f32[64,128,128]{2,1,0:T(8,128)} fusion(%gte.1), kind=kLoop, metadata={op_name="copy(y)"}
}

ENTRY %main.6 (fields_0_.1: f32[64,128,128], fields_1_.1: f32[64,128,128], params_dt.1: f32[], faces_0_.1: f32[2,128,128]) -> (f32[64,128,128], f32[64,128,128]) {
  ROOT %copy.30 = f32[64,128,128]{2,1,0} copy(%while)
}
"""


def test_field_copies_census():
    """Whole-field ``copy`` and ``copy-start`` count (here three: the
    loop copy, the async pair's start, the ROOT copy); face-sized,
    scalar and other-dtype copies, ``copy-done`` and a non-copy op
    whose metadata says "copy(" do not."""
    assert obs_xstats.field_copies(_COPY_HLO, 2) == 3
    assert obs_xstats.field_copies("add mul", 2) == 0

    class Compiled:
        def as_text(self):
            return _COPY_HLO

    rec = obs_xstats.capture(Compiled(), name="r", compile_s=0.1,
                             n_fields=2)
    assert rec["field_copies"] == 3
    assert "field_copies" not in obs_xstats.capture(
        Compiled(), name="r", compile_s=0.1
    )


_RELAYOUT_HLO = """\
HloModule jit_gs_step, input_output_alias={ {0}: (0, {}, may-alias) }

%fused_computation.7 (param_0.1: f32[64,128,128]) -> f32[64,144,128] {
  %param_0.1 = f32[64,128,128]{2,1,0} parameter(0)
  %constant.2 = f32[] constant(0)
  %concatenate.9 = f32[64,256,128]{2,1,0} concatenate(%param_0.1, %param_0.1), dimensions={1}
  ROOT %pad.4 = f32[64,144,128]{2,1,0:T(8,128)} pad(%param_0.1, %constant.2), padding=0_0x8_8x0_0
}

%fused_computation.8 (param_0.2: f32[64,128,128], param_1.2: f32[2,128,128]) -> f32[64,128,128] {
  %param_0.2 = f32[64,128,128]{2,1,0} parameter(0)
  %param_1.2 = f32[2,128,128]{2,1,0} parameter(1)
  %constant.3 = s32[] constant(0)
  ROOT %dynamic-update-slice.1 = f32[64,128,128]{2,1,0} dynamic-update-slice(%param_0.2, %param_1.2, %constant.3, %constant.3, %constant.3)
}

%body (p: (s32[], f32[64,128,128], f32[64,128,128])) -> (s32[], f32[64,128,128], f32[64,128,128]) {
  %pad.1187 = f32[64,136,128]{2,1,0:T(8,128)} pad(f32[64,128,128]{2,1,0:T(8,128)} %gte.1, f32[] %c), padding=0_0x4_4x0_0
  %slice.3071 = f32[64,128,128]{2,1,0:T(8,128)} slice(%out.1), slice={[0:64], [4:132], [0:128]}
  %fusion.5 = f32[64,144,128]{2,1,0:T(8,128)} fusion(%gte.2), kind=kLoop, calls=%fused_computation.7
  %slice.12 = f32[2,136,128]{2,1,0:T(8,128)} slice(%pad.1187), slice={[0:2], [0:136], [0:128]}
  %concatenate.3 = f32[4,128,128]{2,1,0} concatenate(%a, %b), dimensions={0}
  %fusion.6 = f32[64,128,128]{2,1,0:T(8,128)} fusion(%gte.1, %face), kind=kLoop, calls=%fused_computation.8
  %slice.13 = bf16[64,128,128]{2,1,0} slice(%cast), slice={[0:64], [0:128], [0:128]}
}

ENTRY %main.6 (fields_0_.1: f32[64,128,128], fields_1_.1: f32[64,128,128], params_dt.1: f32[]) -> (f32[64,128,128], f32[64,128,128]) {
  ROOT %tuple.1 = (f32[64,128,128]{2,1,0}, f32[64,128,128]{2,1,0}) tuple(%x, %y)
}
"""


def test_field_relayouts_census():
    """Field-size ``pad``, ``slice`` and ``concatenate`` results, alone
    or as a fusion's root: here the y pad, the slice of the owned rows,
    the fusion rooted in a pad, and a bf16 slice of field size — four.
    The face-sized slice and concatenate, the in-place
    dynamic-update-slice fusion, the ops inside a fusion other than its
    root, and the tuple ROOT are not counted."""
    assert obs_xstats.field_relayouts(_RELAYOUT_HLO, 2) == 4
    assert obs_xstats.field_relayouts(_COPY_HLO, 2) == 0
    assert obs_xstats.field_relayouts("add mul", 2) == 0

    class Compiled:
        def as_text(self):
            return _RELAYOUT_HLO

    rec = obs_xstats.capture(Compiled(), name="r", compile_s=0.1,
                             n_fields=2)
    assert rec["field_relayouts"] == 4
    assert "field_relayouts" not in obs_xstats.capture(
        Compiled(), name="r", compile_s=0.1
    )


def test_capture_degrades_on_alien_compiled_object():
    class Alien:
        def cost_analysis(self):
            raise RuntimeError("version drift")

    rec = obs_xstats.capture(Alien(), name="r", compile_s=0.5)
    assert rec["name"] == "r" and rec["compile_s"] == 0.5
    assert "cost" not in rec and "cache" not in rec


def test_capture_cache_outcomes(tmp_path):
    class NoAnalytics:
        pass

    d = tmp_path / "cache"
    d.mkdir()
    before = obs_xstats.cache_listing(str(d))
    (d / "entry0").write_text("x")
    rec = obs_xstats.capture(NoAnalytics(), name="r", compile_s=0.1,
                             cache_dir=str(d), cache_before=before)
    assert rec["cache"] == "miss"
    before = obs_xstats.cache_listing(str(d))
    rec = obs_xstats.capture(NoAnalytics(), name="r2", compile_s=0.1,
                             cache_dir=str(d), cache_before=before)
    assert rec["cache"] == "hit"
    rec = obs_xstats.capture(NoAnalytics(), name="r3", compile_s=0.1,
                             cache_dir=str(d), cache_before=None)
    assert rec["cache"] == "unknown"
    assert obs_xstats.summarize([
        {"cache": "miss", "compile_s": 0.1},
        {"cache": "hit", "compile_s": 0.2},
    ]) == {"compiles": 2, "compile_s_total": 0.3,
           "compile_cache_hits": 1, "compile_cache_misses": 1}


# ------------------------------------------- in-graph (real Simulation)


def _sim(L=8, steps_env=None, **kw):
    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.simulation import Simulation

    s = Settings(L=L, Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0,
                 noise=0.1, precision="Float32", backend="CPU",
                 kernel_language="Plain", **kw)
    return Simulation(s, n_devices=1)


def test_fused_probe_matches_numpy_ground_truth():
    sim = _sim()
    sim.iterate(4)
    snap = sim.snapshot_async(health=True, numerics=True)
    rep = snap.numerics_report()
    assert snap.health_report() is not None  # both probes fused
    for name, arr in zip(("u", "v"), sim.get_fields()):
        got = rep.fields[name]
        assert got["min"] == pytest.approx(float(arr.min()), rel=1e-6)
        assert got["max"] == pytest.approx(float(arr.max()), rel=1e-6)
        assert got["mean"] == pytest.approx(float(arr.mean()), rel=1e-5)
        assert got["l2"] == pytest.approx(
            float(np.sqrt((arr.astype(np.float64) ** 2).sum())),
            rel=1e-5,
        )
        assert got["nonfinite"] == 0
    # probe-only path agrees with the fused one
    rep2 = sim.numerics_stats()
    assert rep2.fields == rep.fields


def test_probe_counts_nonfinite_cells():
    sim = _sim()
    sim.iterate(2)
    sim.poison_nan("u")
    rep = sim.numerics_stats()
    assert rep.fields["u"]["nonfinite"] == 1
    assert rep.fields["v"]["nonfinite"] == 0
    assert rep.finite is False


def test_numerics_probe_leaves_trajectory_bitwise(tmp_path):
    a, b = _sim(), _sim()
    a.iterate(6)
    b.iterate(3)
    b.snapshot_async(health=True, numerics=True)
    b.numerics_stats()
    b.iterate(3)
    for fa, fb in zip(a.get_fields(), b.get_fields()):
        np.testing.assert_array_equal(fa, fb)


def test_xstats_instrumented_runner_bitwise_and_captured(monkeypatch):
    monkeypatch.setenv("GS_XSTATS", "1")
    a = _sim()
    monkeypatch.delenv("GS_XSTATS")
    b = _sim()
    assert a.xstats_enabled and not b.xstats_enabled
    a.iterate(5)
    b.iterate(5)
    for fa, fb in zip(a.get_fields(), b.get_fields()):
        np.testing.assert_array_equal(fa, fb)
    (rec,) = a.executables
    assert rec["name"] == "runner[5]" and rec["nsteps"] == 5
    assert rec["compile_s"] > 0
    assert rec["cost"]["flops"] > 0
    assert rec["memory"]["peak_bytes_estimate"] > 0
    assert rec["collectives"] == {}  # single device: none
    assert json.dumps(rec)  # JSON-able end to end
    assert b.executables == []


def test_xstats_counts_sharded_collectives(monkeypatch):
    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.simulation import Simulation

    monkeypatch.setenv("GS_XSTATS", "1")
    s = Settings(L=16, Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0,
                 noise=0.0, precision="Float32", backend="CPU",
                 kernel_language="Plain")
    sim = Simulation(s, n_devices=8)
    sim.iterate(2)
    (rec,) = sim.executables
    # the 3D halo exchange is built from ppermutes: the census must
    # see collective-permutes in the sharded runner's HLO
    assert rec["collectives"].get("collective-permute", 0) > 0
