"""Tests that require real TPU hardware (Mosaic-compiled kernels).

Skipped everywhere else — the analog of the reference's
``if CUDA.functional()`` hardware gate (``unit-Simulation_CUDA.jl:25``).
Run on the chip with ``GS_TPU_TESTS=1 python -m pytest
tests/unit/test_tpu_hardware.py`` (the variable stops the test conftest
from pinning the CPU). Everything runs in the pytest process: a chip
belongs to one process, so a child started here could not reach it.
"""

import numpy as np
import pytest

import jax

requires_tpu = pytest.mark.skipif(
    jax.default_backend() != "tpu", reason="needs real TPU hardware"
)


def _gs_spec():
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import kernelgen

    return kernelgen.get_spec(grayscott.MODEL)


@requires_tpu
def test_in_kernel_noise_statistics():
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import pallas_stencil

    L, noise = 128, 0.5
    s = Settings(L=L, noise=noise, precision="Float32", backend="TPU",
                 kernel_language="Pallas", Du=0.2, Dv=0.1, F=0.02, k=0.048,
                 dt=1.0)
    dtype = jnp.float32
    params = grayscott.Params.from_settings(s, dtype)
    u, v = grayscott.init_fields(L, dtype)
    seeds = jnp.asarray([123, 456, 7], jnp.int32)

    spec = _gs_spec()
    u1, v1 = pallas_stencil.fused_step((u, v), params, seeds, spec=spec,
                                       use_noise=True)
    u0, v0 = pallas_stencil.fused_step((u, v), params, seeds, spec=spec,
                                       use_noise=False)

    np.testing.assert_allclose(np.asarray(v1), np.asarray(v0), rtol=1e-6)
    unit = (np.asarray(u1) - np.asarray(u0)) / (noise * float(params.dt))
    assert np.all(unit >= -1.0 - 1e-5) and np.all(unit <= 1.0 + 1e-5)
    n = unit.size
    assert abs(unit.mean()) < 4.0 / np.sqrt(n)
    assert abs(unit.std() - 1 / np.sqrt(3)) < 0.01
    # Position keying must not repeat the stream across slabs.
    bx = pallas_stencil.pick_block_planes(L, L, L, 4)
    if bx < L:
        assert not np.array_equal(unit[:bx], unit[bx:2 * bx])

    # Reproducibility: same seeds -> identical draw.
    u1b, _ = pallas_stencil.fused_step((u, v), params, seeds, spec=spec,
                                       use_noise=True)
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u1b))


@requires_tpu
def test_mosaic_noise_matches_xla_stream():
    """The Mosaic-compiled hash noise must reproduce the XLA stream
    bit-for-bit on hardware — the property that makes every off-hardware
    noise test representative of the TPU path."""
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import pallas_stencil

    L = 128
    s = Settings(L=L, noise=0.5, precision="Float32", backend="TPU",
                 kernel_language="Pallas", Du=0.2, Dv=0.1, F=0.02, k=0.048,
                 dt=1.0)
    dtype = jnp.float32
    params = grayscott.Params.from_settings(s, dtype)
    u, v = grayscott.init_fields(L, dtype)
    seeds = jnp.asarray([11, 22, 33], jnp.int32)

    spec = _gs_spec()
    got_u, got_v = pallas_stencil.fused_step((u, v), params, seeds,
                                             spec=spec, use_noise=True)
    want_u, want_v = pallas_stencil._xla_fallback((u, v), params, seeds,
                                                  None, spec=spec,
                                                  use_noise=True)
    np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                               rtol=1e-6, atol=5e-7)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-6, atol=5e-7)


@requires_tpu
@pytest.mark.parametrize("fuse", [2, 4])
def test_temporal_blocking_with_noise_on_hardware(fuse):
    """fuse=k with in-kernel noise vs k fuse=1 steps, Mosaic-compiled —
    the per-stage seeding the off-hardware interpret tests cover must
    hold on the real kernel too."""
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import pallas_stencil

    L = 128
    s = Settings(L=L, noise=0.25, precision="Float32", backend="TPU",
                 kernel_language="Pallas", Du=0.2, Dv=0.1, F=0.02, k=0.048,
                 dt=1.0)
    dtype = jnp.float32
    params = grayscott.Params.from_settings(s, dtype)
    u, v = grayscott.init_fields(L, dtype)
    seeds = jnp.asarray([5, 6, 0], jnp.int32)

    spec = _gs_spec()
    uk, vk = pallas_stencil.fused_step((u, v), params, seeds, spec=spec,
                                       use_noise=True, fuse=fuse)
    us, vs = u, v
    for step in range(fuse):
        us, vs = pallas_stencil.fused_step(
            (us, vs), params, seeds.at[2].add(step), spec=spec,
            use_noise=True)
    np.testing.assert_allclose(np.asarray(uk), np.asarray(us),
                               rtol=1e-6, atol=5e-7)
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vs),
                               rtol=1e-6, atol=5e-7)


@requires_tpu
def test_cli_end_to_end_on_hardware(tmp_path, monkeypatch):
    """The full product path — TOML config -> CLI entry -> driver ->
    fused Pallas step loop -> BP-lite + .vti output — on the real chip
    (the reference's functional test, ``functional-GrayScott.jl:4-11``,
    run on the target hardware instead of CI CPUs), in this process."""
    from grayscott_jl_tpu import julia_main

    cfg = tmp_path / "config.toml"
    # L=128 + kernel_language=Pallas: the lane-alignment gate routes
    # L=64 to the XLA kernel on TPU, and Settings defaults to Plain —
    # both would silently turn this into a Plain/XLA CLI test.
    cfg.write_text(
        'L = 128\nDu = 0.2\nDv = 0.1\nF = 0.02\nk = 0.048\ndt = 1.0\n'
        'plotgap = 10\nsteps = 20\nnoise = 0.1\noutput = "out.bp"\n'
        'mesh_type = "image"\nprecision = "Float32"\nbackend = "TPU"\n'
        'kernel_language = "Pallas"\n'
    )
    monkeypatch.chdir(tmp_path)
    assert julia_main(["config.toml"]) == 0

    from grayscott_jl_tpu.io.bplite import BpReader

    r = BpReader(str(tmp_path / "out.bp"))
    assert r.num_steps() == 2
    u = r.get("U", step=1)
    assert u.shape == (128, 128, 128)
    assert np.isfinite(u).all()
    # ParaView-openable side-channel: .vti frames + series index
    # (VtiSeriesWriter writes <base>.vtk/series.pvd + step_*.vti).
    assert (tmp_path / "out.vtk" / "series.pvd").exists()
    assert any((tmp_path / "out.vtk").glob("step_*.vti"))


@requires_tpu
@pytest.mark.parametrize("noise", [0.0, 0.25])
def test_faces_kernel_on_hardware(noise):
    """The with-faces (sharded-block) kernel — face DMAs + in-register
    edge repair from neighbor slabs — Mosaic-compiled against the XLA
    pad-from-faces oracle. Single-chip can't shard, but the kernel
    itself is identical under shard_map; this is the hardware coverage
    for the distributed kernel combination (off-hardware it runs only
    under the interpreter)."""
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import pallas_stencil

    L = 128
    s = Settings(L=L, noise=noise, precision="Float32", backend="TPU",
                 kernel_language="Pallas", Du=0.2, Dv=0.1, F=0.02, k=0.048,
                 dt=1.0)
    dtype = jnp.float32
    params = grayscott.Params.from_settings(s, dtype)
    key = jax.random.PRNGKey(3)
    keys = jax.random.split(key, 14)
    u = jax.random.uniform(keys[0], (L, L, L), dtype)
    v = jax.random.uniform(keys[1], (L, L, L), dtype)
    shapes = [(1, L, L)] * 4 + [(L, 1, L)] * 4 + [(L, L, 1)] * 4
    faces = tuple(
        jax.random.uniform(k, sh, dtype) for k, sh in zip(keys[2:], shapes)
    )
    seeds = jnp.asarray([3, 1, 9], jnp.int32)
    use_noise = noise != 0.0

    # Guard against vacuity: if fused_step would take its own XLA
    # fallback (VMEM-too-small part, lane misalignment), this test
    # compares the oracle with itself and proves nothing.
    assert pallas_stencil.pick_block_planes(L, L, L, 4, 1) > 0
    assert L % 128 == 0, "lane-misaligned L would route to XLA"

    spec = _gs_spec()
    got_u, got_v = pallas_stencil.fused_step(
        (u, v), params, seeds, faces, spec=spec, use_noise=use_noise
    )
    want_u, want_v = pallas_stencil._xla_fallback(
        (u, v), params, seeds, faces, spec=spec, use_noise=use_noise
    )
    np.testing.assert_allclose(np.asarray(got_u), np.asarray(want_u),
                               rtol=1e-6, atol=5e-7)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-6, atol=5e-7)


@requires_tpu
def test_bfloat16_pallas_on_hardware():
    """BFloat16 fields with f32 SMEM params must Mosaic-compile and track
    the f32 trajectory to bf16 precision (the SMEM-dtype contract the
    off-hardware tests can only exercise in interpret mode)."""
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.simulation import Simulation

    common = dict(L=128, noise=0.0, Du=0.2, Dv=0.1, F=0.02, k=0.048,
                  dt=1.0, backend="TPU", kernel_language="Pallas")
    a = Simulation(Settings(precision="Float32", **common), n_devices=1)
    b = Simulation(Settings(precision="BFloat16", **common), n_devices=1)
    a.iterate(10)
    b.iterate(10)
    ua = a.get_fields()[0]
    ub = b.get_fields()[0].astype(np.float32)
    assert np.isfinite(ub).all()
    np.testing.assert_allclose(ua, ub, rtol=0.05, atol=0.05)


@requires_tpu
@pytest.mark.parametrize("noise", [0.0, 0.1])
def test_pallas_matches_xla_on_tpu(noise):
    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.simulation import Simulation

    common = dict(L=128, noise=noise, precision="Float32", backend="TPU",
                  Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0)
    a = Simulation(Settings(kernel_language="Plain", **common), n_devices=1)
    b = Simulation(Settings(kernel_language="Pallas", **common), n_devices=1)
    a.iterate(10)
    b.iterate(10)
    np.testing.assert_allclose(
        a.get_fields()[0], b.get_fields()[0], rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        a.get_fields()[1], b.get_fields()[1], rtol=1e-5, atol=1e-6
    )


@requires_tpu
def test_auto_dispatch_on_hardware():
    """kernel_language = "Auto" on the real chip (r5): a 128-aligned
    f32 single-chip config must resolve to the Pallas kernel (and run
    it — agreement with the XLA kernel to f32 roundoff), a misaligned
    or f64 config to XLA, openly."""
    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.simulation import Simulation

    common = dict(L=128, noise=0.1, precision="Float32", backend="TPU",
                  Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0)
    auto = Simulation(Settings(kernel_language="Auto", **common),
                      n_devices=1)
    assert auto.kernel_language == "pallas"
    assert auto.kernel_selection["platform"] == "tpu"
    auto.iterate(10)
    ref = Simulation(Settings(kernel_language="Plain", **common),
                     n_devices=1)
    ref.iterate(10)
    np.testing.assert_allclose(
        np.asarray(auto.get_fields()[0]), np.asarray(ref.get_fields()[0]),
        rtol=1e-5, atol=1e-6,
    )

    # Mosaic gates resolve to XLA openly (the kernel would silently
    # fall back at these configs; the label must match what executes).
    mis = Simulation(
        Settings(**{**common, "L": 64, "kernel_language": "Auto"}),
        n_devices=1,
    )
    assert mis.kernel_language == "xla"
    # resolve_precision flips the jax_enable_x64 global; restore it so
    # the remaining hardware tests run in the same JAX mode they see
    # when run alone.
    prev_x64 = jax.config.jax_enable_x64
    try:
        f64 = Simulation(
            Settings(**{**common, "precision": "Float64",
                        "kernel_language": "Auto"}),
            n_devices=1,
        )
        assert f64.kernel_language == "xla"
    finally:
        jax.config.update("jax_enable_x64", prev_x64)


@requires_tpu
def test_x_chain_kernel_on_hardware():
    """The Mosaic-compiled x-chain (fuse-wide x faces feeding the
    in-kernel temporal chain — the 1D-sharded mode's kernel) against
    the XLA x-chain fallback on real hardware, noise on, multi-slab
    (L=256 local block, bx=16). Catches Mosaic-only lowering faults in
    the face-DMA width generalization and the global-coordinate ring
    pinning that interpret mode cannot."""
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import pallas_stencil

    nx = ny = nz = 256
    k = 5
    s = Settings(L=nx, noise=0.2, precision="Float32", backend="TPU",
                 kernel_language="Pallas", Du=0.2, Dv=0.1, F=0.02,
                 k=0.048, dt=1.0)
    dtype = jnp.float32
    params = grayscott.Params.from_settings(s, dtype)
    key = jax.random.PRNGKey(42)
    u = jax.random.uniform(key, (nx, ny, nz), dtype)
    v = jax.random.uniform(jax.random.fold_in(key, 1), (nx, ny, nz), dtype)
    faces = tuple(
        jax.random.uniform(jax.random.fold_in(key, 2 + i), (k, ny, nz),
                           dtype)
        for i in range(4)
    )
    seeds = jnp.asarray([5, 9, 31], jnp.int32)
    offs = jnp.asarray([256, 0, 0], jnp.int32)  # interior shard
    row = jnp.int32(1024)

    spec = _gs_spec()
    a = pallas_stencil.fused_step(
        (u, v), params, seeds, faces, spec=spec, use_noise=True, fuse=k,
        offsets=offs, row=row,
    )
    b = pallas_stencil._xla_xchain_fallback(
        (u, v), params, seeds, faces, spec=spec, fuse=k, use_noise=True,
        offsets=offs, row=row,
    )
    np.testing.assert_allclose(
        np.asarray(a[0]), np.asarray(b[0]), rtol=1e-4, atol=2e-6
    )
    np.testing.assert_allclose(
        np.asarray(a[1]), np.asarray(b[1]), rtol=1e-4, atol=2e-6
    )

    # and the bv-faces <-> no-faces bitwise identity on Mosaic
    from grayscott_jl_tpu.models import grayscott as st

    bfaces = tuple(
        jnp.full((k, ny, nz), b, dtype)
        for b in (st.U_BOUNDARY, st.U_BOUNDARY, st.V_BOUNDARY,
                  st.V_BOUNDARY)
    )
    offs0 = jnp.zeros((3,), jnp.int32)
    c = pallas_stencil.fused_step(
        (u, v), params, seeds, bfaces, spec=spec, use_noise=True, fuse=k,
        offsets=offs0, row=jnp.int32(nx),
    )
    d = pallas_stencil.fused_step(
        (u, v), params, seeds, spec=spec, use_noise=True, fuse=k,
        offsets=offs0, row=jnp.int32(nx),
    )
    np.testing.assert_array_equal(np.asarray(c[0]), np.asarray(d[0]))
    np.testing.assert_array_equal(np.asarray(c[1]), np.asarray(d[1]))


@requires_tpu
def test_xy_chain_kernel_on_hardware():
    """The Mosaic-compiled xy-chain (round 4): the owned rows in place
    plus a sublane tile of y halo per side, assembled in VMEM — global
    y origin of the plane negative — through the in-kernel chain with
    global-(x,y) mid-stage ring pinning, against the XLA xy-chain
    fallback on the same plane. This is the kernel the (n, m, 1) pod
    meshes launch; catches Mosaic lowering faults in the widened-plane
    slab walk and the halo DMAs that interpret mode cannot."""
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import pallas_stencil

    nx, nz, k = 128, 256, 4
    ny = 128
    hy = pallas_stencil.y_halo_rows(k)
    s = Settings(L=512, noise=0.2, precision="Float32", backend="TPU",
                 kernel_language="Pallas", Du=0.2, Dv=0.1, F=0.02,
                 k=0.048, dt=1.0)
    dtype = jnp.float32
    params = grayscott.Params.from_settings(s, dtype)
    key = jax.random.PRNGKey(17)
    u = jax.random.uniform(key, (nx, ny, nz), dtype)
    v = jax.random.uniform(jax.random.fold_in(key, 1), (nx, ny, nz), dtype)
    faces = tuple(
        jax.random.uniform(jax.random.fold_in(key, 2 + i),
                           (k, ny + 2 * hy, nz), dtype)
        for i in range(4)
    )
    y_halos = tuple(
        jax.random.uniform(jax.random.fold_in(key, 6 + i), (nx, hy, nz),
                           dtype)
        for i in range(4)
    )
    seeds = jnp.asarray([8, 4, 12], jnp.int32)
    # Interior shard in x and y of the 512^3 global grid.
    offs = jnp.asarray([128, 128, 0], jnp.int32)
    row = jnp.int32(512)

    spec = _gs_spec()
    a = pallas_stencil.fused_step(
        (u, v), params, seeds, faces, spec=spec, use_noise=True, fuse=k,
        offsets=offs, row=row, y_halos=y_halos,
    )
    planes = tuple(
        jnp.concatenate([y_halos[2 * i], f, y_halos[2 * i + 1]], axis=1)
        for i, f in enumerate((u, v))
    )
    b = pallas_stencil._xla_xchain_fallback(
        planes, params, seeds, faces, spec=spec, fuse=k, use_noise=True,
        offsets=offs.at[1].add(-hy), row=row,
    )
    # The kernel returns the owned rows; the fallback's halo rows carry
    # ring values that no caller reads.
    np.testing.assert_allclose(
        np.asarray(a[0]), np.asarray(b[0][:, hy:hy + ny]),
        rtol=1e-4, atol=2e-6,
    )
    np.testing.assert_allclose(
        np.asarray(a[1]), np.asarray(b[1][:, hy:hy + ny]),
        rtol=1e-4, atol=2e-6,
    )
