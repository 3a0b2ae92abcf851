"""The chain-schedule table (``parallel/schedule.select``): which round
a runner drives, and at what depth, for each kernel language, mesh,
step count, fuse base and ``halo_depth``. Pure host arithmetic — no
trace, no device."""

import pytest

import jax.numpy as jnp

from grayscott_jl_tpu.ops import pallas_stencil
from grayscott_jl_tpu.parallel import schedule

BLOCK = schedule.pallas_block_round
SHARDED = schedule.pallas_sharded_round
STEP = schedule.xla_step_round
WINDOW = schedule.xla_window_round


@pytest.mark.parametrize(
    "lang,L,mesh,nsteps,fuse,halo_depth,overlap,want,depth,warns", [
        pytest.param("pallas", 256, (1, 1, 1), 200, 5, 1, False, BLOCK, 5,
                     False, id="one-chip-fuse"),
        pytest.param("pallas", 256, (1, 1, 1), 3, 5, 1, False, BLOCK, 3,
                     False, id="one-chip-nsteps-below-fuse"),
        pytest.param("pallas", 32, (1, 1, 1), 0, 5, 1, False, BLOCK, 1,
                     False, id="one-chip-no-steps"),
        pytest.param("pallas", 32, (8, 1, 1), 200, 5, 1, True, SHARDED, 4,
                     False, id="8x1x1-capped-at-local-nx"),
        pytest.param("pallas", 64, (8, 1, 1), 200, 3, 2, True, SHARDED, 6,
                     False, id="8x1x1-halo-depth-2"),
        pytest.param("pallas", 64, (8, 1, 1), 200, 5, 2, True, SHARDED, 8,
                     False, id="8x1x1-halo-depth-2-capped-at-local-nx"),
        pytest.param("pallas", 32, (4, 2, 1), 200, 2, 2, True, SHARDED, 4,
                     False, id="4x2x1-halo-depth-2"),
        pytest.param("pallas", 32, (4, 2, 1), 1, 5, 1, True, SHARDED, 1,
                     False, id="4x2x1-nsteps-below-fuse"),
        pytest.param("pallas", 16, (2, 2, 2), 200, 5, 1, True, SHARDED, 4,
                     False, id="2x2x2-capped-at-half-nz"),
        pytest.param("pallas", 8, (1, 1, 8), 200, 5, 1, True, SHARDED, 1,
                     False, id="z-sharded-nz-1-floored-at-1"),
        pytest.param("pallas", 1024, (2, 2, 1), 200, 5, 1, True, SHARDED,
                     2, True, id="2x2x1-L1024-vmem-capped"),
        pytest.param("xla", 256, (1, 1, 1), 200, 5, 1, False, STEP, 1,
                     False, id="xla-one-chip"),
        pytest.param("xla", 16, (2, 2, 2), 200, 5, 1, True, WINDOW, 5,
                     False, id="xla-window"),
        pytest.param("xla", 32, (4, 2, 1), 200, 2, 2, True, WINDOW, 4,
                     False, id="xla-window-halo-depth-2"),
        pytest.param("xla", 32, (8, 1, 1), 200, 5, 1, True, WINDOW, 4,
                     False, id="xla-window-capped-at-local-extent"),
        pytest.param("xla", 16, (2, 2, 2), 1, 5, 1, True, STEP, 1,
                     False, id="xla-one-step-multi-axis"),
        pytest.param("xla", 32, (8, 1, 1), 1, 5, 1, True, WINDOW, 1,
                     False, id="xla-one-step-split-phase-window"),
    ])
def test_select_schedule(monkeypatch, capsys, lang, L, mesh, nsteps, fuse,
                         halo_depth, overlap, want, depth, warns):
    """Each row of the table, with the caps that bound a sharded chain:
    the local block's geometry (x extent, y extent, half the z extent
    where z is sharded), ``nsteps``, and the VMEM slab budget — the one
    cap that warns, once."""
    monkeypatch.setattr(pallas_stencil, "_WARNED", set())
    local = tuple(L // d for d in mesh)
    for _ in range(2):
        got = schedule.select(
            lang, mesh != (1, 1, 1), nsteps=nsteps, fuse=fuse,
            halo_depth=halo_depth, local=local, dims=mesh,
            dtype=jnp.float32, n_fields=2, overlap=overlap,
        )
        assert got == (want, depth)
    capped = capsys.readouterr().err.count("depth capped")
    assert capped == (1 if warns else 0)
