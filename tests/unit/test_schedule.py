"""The chain-schedule table (``parallel/schedule.select``): which round
a runner drives, at what depth and, for the one-chip XLA step, in
x-slabs of how many planes, for each kernel language, mesh, step
count, fuse base and ``halo_depth``. Pure host arithmetic — no trace,
no device — but for the slab count of a compiled runner's record."""

import pytest

import jax.numpy as jnp

from grayscott_jl_tpu.ops import pallas_stencil
from grayscott_jl_tpu.parallel import schedule

BLOCK = schedule.pallas_block_round
SHARDED = schedule.pallas_sharded_round
STEP = schedule.xla_step_round
WINDOW = schedule.xla_window_round
SLABS = schedule.xla_slab_round


@pytest.mark.parametrize(
    "lang,L,mesh,nsteps,fuse,halo_depth,overlap,want,depth,slab,warns", [
        pytest.param("pallas", 256, (1, 1, 1), 200, 5, 1, False, BLOCK, 5,
                     None, False, id="one-chip-fuse"),
        pytest.param("pallas", 256, (1, 1, 1), 3, 5, 1, False, BLOCK, 3,
                     None, False, id="one-chip-nsteps-below-fuse"),
        pytest.param("pallas", 32, (1, 1, 1), 0, 5, 1, False, BLOCK, 1,
                     None, False, id="one-chip-no-steps"),
        pytest.param("pallas", 32, (8, 1, 1), 200, 5, 1, True, SHARDED, 4,
                     None, False, id="8x1x1-capped-at-local-nx"),
        pytest.param("pallas", 64, (8, 1, 1), 200, 3, 2, True, SHARDED, 6,
                     None, False, id="8x1x1-halo-depth-2"),
        pytest.param("pallas", 64, (8, 1, 1), 200, 5, 2, True, SHARDED, 8,
                     None, False, id="8x1x1-halo-depth-2-capped-at-local-nx"),
        pytest.param("pallas", 32, (4, 2, 1), 200, 2, 2, True, SHARDED, 4,
                     None, False, id="4x2x1-halo-depth-2"),
        pytest.param("pallas", 32, (4, 2, 1), 1, 5, 1, True, SHARDED, 1,
                     None, False, id="4x2x1-nsteps-below-fuse"),
        pytest.param("pallas", 16, (2, 2, 2), 200, 5, 1, True, SHARDED, 4,
                     None, False, id="2x2x2-capped-at-half-nz"),
        pytest.param("pallas", 8, (1, 1, 8), 200, 5, 1, True, SHARDED, 1,
                     None, False, id="z-sharded-nz-1-floored-at-1"),
        pytest.param("pallas", 1024, (2, 2, 1), 200, 5, 1, True, SHARDED,
                     2, None, True, id="2x2x1-L1024-vmem-capped"),
        pytest.param("xla", 256, (1, 1, 1), 200, 5, 1, False, SLABS, 1, 16,
                     False, id="xla-one-chip"),
        pytest.param("xla", 128, (1, 1, 1), 200, 5, 1, False, STEP, 1,
                     None, False, id="xla-one-chip-L128-whole"),
        pytest.param("xla", 64, (1, 1, 1), 200, 5, 1, False, STEP, 1,
                     None, False, id="xla-one-chip-L64-whole"),
        pytest.param("xla", 16, (2, 2, 2), 200, 5, 1, True, WINDOW, 5,
                     None, False, id="xla-window"),
        pytest.param("xla", 32, (4, 2, 1), 200, 2, 2, True, WINDOW, 4,
                     None, False, id="xla-window-halo-depth-2"),
        pytest.param("xla", 32, (8, 1, 1), 200, 5, 1, True, WINDOW, 4,
                     None, False, id="xla-window-capped-at-local-extent"),
        pytest.param("xla", 16, (2, 2, 2), 1, 5, 1, True, STEP, 1,
                     None, False, id="xla-one-step-multi-axis"),
        pytest.param("xla", 512, (2, 1, 1), 1, 5, 1, False, STEP, 1,
                     None, False, id="xla-one-step-sharded-never-in-slabs"),
        pytest.param("xla", 32, (8, 1, 1), 1, 5, 1, True, WINDOW, 1,
                     None, False, id="xla-one-step-split-phase-window"),
    ])
def test_select_schedule(monkeypatch, capsys, lang, L, mesh, nsteps, fuse,
                         halo_depth, overlap, want, depth, slab, warns):
    """Each row of the table, with the caps that bound a sharded chain:
    the local block's geometry (x extent, y extent, half the z extent
    where z is sharded), ``nsteps``, and the VMEM slab budget — the one
    cap that warns, once. The one-chip XLA step runs whole up to L=128
    and in 16-plane slabs at L=256; the sharded single step, on its
    exchanged faces, never in slabs, however large its block. Every
    XLA schedule counts its slabs per step (1 where the step runs
    whole); a Pallas one counts none."""
    monkeypatch.setattr(pallas_stencil, "_WARNED", set())
    local = tuple(L // d for d in mesh)
    slabs = (None if lang == "pallas" else
             1 if slab is None else -(-local[0] // slab))
    for _ in range(2):
        got = schedule.select(
            lang, mesh != (1, 1, 1), nsteps=nsteps, fuse=fuse,
            halo_depth=halo_depth, local=local, dims=mesh,
            dtype=jnp.float32, n_fields=2, overlap=overlap,
        )
        assert got == (want, depth, slab, slabs)
    capped = capsys.readouterr().err.count("depth capped")
    assert capped == (1 if warns else 0)


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32, jnp.bfloat16])
def test_slab_depth_is_the_same_in_each_precision(dtype):
    """L=256 takes 16-plane slabs in every precision: Float64 counts as
    its float32 halves, each the size of a Float32 slab array."""
    got = schedule.select(
        "xla", False, nsteps=200, fuse=5, halo_depth=1,
        local=(256, 256, 256), dims=(1, 1, 1), dtype=dtype, n_fields=2,
        overlap=False,
    )
    assert got.round is SLABS
    assert got.slab == 16 and got.step_slabs == 16


@pytest.mark.parametrize("local,budget,want", [
    pytest.param((256, 256, 256), None, 16, id="L256"),
    pytest.param((128, 128, 128), None, None, id="L128-two-slabs-whole"),
    pytest.param((512, 512, 512), None, 32, id="L512-capped-at-16-slabs"),
    pytest.param((300, 256, 256), None, 19, id="x-not-divided"),
    pytest.param((1, 2048, 2048), None, None, id="single-plane-whole"),
    pytest.param((16, 16, 16), 768, 3, id="budget-lowered"),
    pytest.param((16, 16, 16), 2048, None, id="budget-two-slabs-whole"),
])
def test_step_slab_depth(local, budget, want):
    """The rule: ``budget`` cells per slab, a block of at most two
    slabs whole, at most ``MAX_STEP_SLABS`` slabs."""
    kw = {} if budget is None else {"budget": budget}
    got = schedule.step_slab_depth(local, **kw)
    assert got == want
    if got is not None:
        assert -(-local[0] // got) <= schedule.MAX_STEP_SLABS


def test_ensemble_member_shape_decides_the_slab_depth(monkeypatch):
    """An ensemble steps its members under ``vmap``; the slab depth
    comes from a member's block, not the member-stacked array. With
    the budget lowered, the members run in slabs and each is still its
    solo run, bitwise."""
    import numpy as np
    from functools import partial

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.ensemble import spec as ens_spec
    from grayscott_jl_tpu.ensemble.engine import EnsembleSimulation
    from grayscott_jl_tpu.ensemble.io import member_settings
    from grayscott_jl_tpu.simulation import Simulation

    ens = Settings(L=16, noise=0.1, precision="Float32", backend="CPU",
                   Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0)
    ens.ensemble = ens_spec.from_toml(
        {"presets": ["spots", "waves"], "member_shards": 1}, ens)
    monkeypatch.setattr(schedule, "step_slab_depth",
                        partial(schedule.step_slab_depth, budget=4 * 256))
    sim = EnsembleSimulation(ens, n_devices=1, seed=7)
    assert sim.domain.local_shape == (16, 16, 16)
    assert sim.step_slabs(4) == 4
    sim.iterate(4)
    ue, ve = sim.get_fields()
    monkeypatch.undo()
    for k in range(2):
        solo = Simulation(member_settings(ens, k), n_devices=1, seed=7 + k)
        assert solo.step_slabs(4) == 1
        solo.iterate(4)
        us, vs = solo.get_fields()
        np.testing.assert_array_equal(ue[k], us)
        np.testing.assert_array_equal(ve[k], vs)


@pytest.mark.parametrize("budget,slabs", [(None, 1), (4 * 32 * 32, 8)])
def test_runner_record_counts_step_slabs(monkeypatch, budget, slabs):
    """``step_slabs`` in a compiled runner's ``obs/xstats`` record: 1 at
    L=32, where the step runs whole, and the slab count under a
    lowered budget; absent on a Pallas runner."""
    from functools import partial

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.simulation import Simulation

    monkeypatch.setenv("GS_XSTATS", "1")
    if budget is not None:
        monkeypatch.setattr(schedule, "step_slab_depth",
                            partial(schedule.step_slab_depth, budget=budget))
    for lang in ("Plain", "Pallas"):
        s = Settings(L=32, noise=0.1, precision="Float32", backend="CPU",
                     kernel_language=lang, Du=0.2, Dv=0.1, F=0.02,
                     k=0.048, dt=1.0)
        sim = Simulation(s, n_devices=1)
        sim.iterate(2)
        (rec,) = sim.executables
        if lang == "Plain":
            assert rec["step_slabs"] == slabs
        else:
            assert "step_slabs" not in rec


def test_step_slabs_reaches_the_metrics_registry():
    """``publish`` mirrors a runner record's ``step_slabs`` into the
    metrics registry as a gauge labelled by runner; a Pallas record,
    which has none, sets no such gauge."""
    from grayscott_jl_tpu.obs import xstats
    from grayscott_jl_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry(enabled=True)
    xstats.publish({"name": "runner[200]", "compile_s": 1.0,
                    "step_slabs": 16}, metrics=reg)
    xstats.publish({"name": "runner[5]", "compile_s": 1.0}, metrics=reg)
    gauges = {(g["name"], g["labels"].get("runner")): g["value"]
              for g in reg.snapshot()["gauges"]}
    assert gauges[("step_slabs", "runner[200]")] == 16
    assert ("step_slabs", "runner[5]") not in gauges
