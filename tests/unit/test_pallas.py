"""Pallas-kernel equivalence tests (interpret mode on CPU).

Runs of the two kernel languages must agree to float tolerance — noisy
runs included, because both kernels draw from the framework's shared
position-keyed noise stream (``ops/noise.py``). This is the strengthened
version of the reference's cross-backend oracle pattern
(``unit-Simulation_CUDA.jl:10-32``), whose CPU and CUDA backends draw
from unrelated RNGs and can only be compared noiselessly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from grayscott_jl_tpu.config.settings import Settings
from grayscott_jl_tpu.models import grayscott
from grayscott_jl_tpu.models import grayscott as gs_model
from grayscott_jl_tpu.ops import kernelgen, pallas_stencil, stencil
from grayscott_jl_tpu.simulation import Simulation

PARAMS = dict(Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0)

# This suite predates the kernel generator and exercises the kernel in
# its historical two-field Gray-Scott shape; the wrappers adapt that
# call shape to the generated-kernel tuple+spec API so every
# refactor-sensitive config here keeps pinning the same program.
SPEC = kernelgen.get_spec(grayscott.MODEL)


def fused_step(u, v, params, seeds, faces=None, **kw):
    return pallas_stencil.fused_step(
        (u, v), params, seeds, faces, spec=SPEC, **kw
    )


def xla_fallback(u, v, params, seeds, faces, **kw):
    return pallas_stencil._xla_fallback(
        (u, v), params, seeds, faces, spec=SPEC, **kw
    )


def xchain_fallback(u, v, params, seeds, faces, **kw):
    return pallas_stencil._xla_xchain_fallback(
        (u, v), params, seeds, faces, spec=SPEC, **kw
    )


def _settings(lang, L=16, noise=0.0, **kw):
    base = dict(
        L=L, noise=noise, precision="Float32", backend="CPU",
        kernel_language=lang, **PARAMS,
    )
    base.update(kw)
    return Settings(**base)


# L=16 -> BX=16 (single-slab path); L=32 -> 2 slabs; L=48 -> 3 slabs
# (pipelined steady state with both buffer slots cycling).
@pytest.mark.parametrize("L", [16, 32, 48])
@pytest.mark.parametrize("noise", [0.0, 0.1])
def test_pallas_matches_xla(L, noise):
    """Cross-kernel-language oracle — exact for noisy runs too (shared
    position-keyed stream)."""
    a = Simulation(_settings("XLA", L=L, noise=noise), n_devices=1, seed=5)
    b = Simulation(_settings("Pallas", L=L, noise=noise), n_devices=1, seed=5)
    a.iterate(10)
    b.iterate(10)
    ua, va = a.get_fields()
    ub, vb = b.get_fields()
    np.testing.assert_allclose(ua, ub, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(va, vb, rtol=1e-6, atol=1e-7)


def test_pallas_float64_interpret():
    a = Simulation(_settings("XLA", precision="Float64"), n_devices=1)
    b = Simulation(_settings("Pallas", precision="Float64"), n_devices=1)
    a.iterate(5)
    b.iterate(5)
    np.testing.assert_allclose(
        a.get_fields()[0], b.get_fields()[0], rtol=1e-12
    )


def test_pallas_noise_statistics_and_reproducibility():
    """One noisy step vs the noiseless step isolates dt*noise*U(-1,1)."""
    L, noise = 32, 0.5
    settings = _settings("Pallas", L=L, noise=noise)
    dtype = jnp.float32
    params = grayscott.Params.from_settings(settings, dtype)
    params0 = grayscott.Params.from_settings(
        _settings("Pallas", L=L, noise=0.0), dtype
    )
    u, v = grayscott.init_fields(L, dtype)
    seeds = jnp.asarray([123, 456, 7], jnp.int32)

    u1, v1 = fused_step(u, v, params, seeds, use_noise=True)
    u0, v0 = fused_step(u, v, params0, seeds, use_noise=False)

    # v never receives noise (Simulation_CPU.jl:101-112).
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v0), rtol=1e-6)

    unit = (np.asarray(u1) - np.asarray(u0)) / (noise * float(params.dt))
    assert np.all(unit >= -1.0 - 1e-5) and np.all(unit <= 1.0 + 1e-5)
    n = unit.size
    assert abs(unit.mean()) < 4.0 / np.sqrt(n)  # mean 0
    assert abs(unit.std() - 1 / np.sqrt(3)) < 0.01  # std of U(-1,1)

    # Same seeds -> identical draw; different step seed -> different draw.
    u1b, _ = fused_step(u, v, params, seeds, use_noise=True)
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(u1b))
    seeds2 = seeds.at[2].set(8)
    u2, _ = fused_step(u, v, params, seeds2, use_noise=True)
    assert not np.array_equal(np.asarray(u1), np.asarray(u2))


def test_temporal_blocking_with_noise_matches_two_single_steps():
    """fuse=2 WITH in-kernel noise must equal two fuse=1 steps with step
    seeds ``s`` and ``s+1`` — asserting the kernel's own noise seeding
    (stage A at seeds[2], stage B at seeds[2]+1, masked ghost-plane
    noise), not post-hoc injection. Off TPU the kernel draws from the
    counter-hash stub, which obeys the identical seeding contract."""
    L = 32
    dtype = jnp.float32
    params = grayscott.Params.from_settings(
        _settings("Pallas", L=L, noise=0.25), dtype
    )
    key = jax.random.PRNGKey(21)
    u = jax.random.uniform(key, (L, L, L), dtype)
    v = jax.random.uniform(jax.random.fold_in(key, 1), (L, L, L), dtype)
    seeds = jnp.asarray([17, 29, 4], jnp.int32)

    u2, v2 = fused_step(
        u, v, params, seeds, use_noise=True, fuse=2
    )
    ua, va = fused_step(u, v, params, seeds, use_noise=True)
    ub, vb = fused_step(
        ua, va, params, seeds.at[2].add(1), use_noise=True
    )
    np.testing.assert_allclose(
        np.asarray(u2), np.asarray(ub), rtol=1e-6, atol=5e-7
    )
    np.testing.assert_allclose(
        np.asarray(v2), np.asarray(vb), rtol=1e-6, atol=5e-7
    )


def test_noise_stream_is_position_keyed_not_layout_keyed():
    """The in-kernel noise is keyed on (key, step, global plane), so the
    noise field must be identical between the with-faces (sharded-block)
    and no-faces (single-block) kernel builds."""
    L = 32
    dtype = jnp.float32
    noisy = grayscott.Params.from_settings(
        _settings("Pallas", L=L, noise=0.5), dtype
    )
    quiet = grayscott.Params.from_settings(_settings("Pallas", L=L), dtype)
    key = jax.random.PRNGKey(13)
    keys = jax.random.split(key, 14)
    u = jax.random.uniform(keys[0], (L, L, L), dtype)
    v = jax.random.uniform(keys[1], (L, L, L), dtype)
    shapes = [(1, L, L)] * 4 + [(L, 1, L)] * 4 + [(L, L, 1)] * 4
    faces = tuple(
        jax.random.uniform(k, s, dtype) for k, s in zip(keys[2:], shapes)
    )
    seeds = jnp.asarray([3, 1, 9], jnp.int32)

    def noise_delta(faces_arg):
        un, _ = fused_step(
            u, v, noisy, seeds, faces_arg, use_noise=True
        )
        u0, _ = fused_step(
            u, v, quiet, seeds, faces_arg, use_noise=False
        )
        return np.asarray(un) - np.asarray(u0)

    np.testing.assert_allclose(
        noise_delta(faces), noise_delta(None), rtol=1e-5, atol=1e-6
    )


def test_temporal_blocking_matches_two_single_steps():
    """fuse=2 (two timesteps per HBM pass, with slab-overlap
    recomputation) must reproduce two fuse=1 steps exactly — the
    per-(step, plane) noise keying makes the streams identical."""
    L = 32
    dtype = jnp.float32
    params = grayscott.Params.from_settings(_settings("Pallas", L=L), dtype)
    key = jax.random.PRNGKey(11)
    u = jax.random.uniform(key, (L, L, L), dtype)
    v = jax.random.uniform(jax.random.fold_in(key, 1), (L, L, L), dtype)
    seeds = jnp.asarray([5, 6, 0], jnp.int32)

    u2, v2 = fused_step(
        u, v, params, seeds, use_noise=False, fuse=2
    )
    ua, va = fused_step(u, v, params, seeds, use_noise=False)
    ub, vb = fused_step(
        ua, va, params, seeds.at[2].add(1), use_noise=False
    )
    np.testing.assert_allclose(
        np.asarray(u2), np.asarray(ub), rtol=1e-6, atol=5e-7
    )
    np.testing.assert_allclose(
        np.asarray(v2), np.asarray(vb), rtol=1e-6, atol=5e-7
    )


@pytest.mark.parametrize("fuse", [3, 4])
@pytest.mark.parametrize("use_noise", [False, True])
def test_deep_temporal_blocking_matches_single_steps(fuse, use_noise):
    """fuse=k (k timesteps per HBM pass via the k-stage shrinking-window
    chain) must reproduce k fuse=1 steps, noise included — stage s
    draws at step seeds[2]+s on the same position-keyed stream.

    Tolerance note: on XLA:CPU (this suite's interpret/fallback
    backend) FP-contraction decisions are shape-structure-sensitive,
    and the k-stage shrinking-window program lowers the same per-cell
    arithmetic through different shapes than the per-step path — FMA
    formation flips per stage and the drift compounds over k steps on
    these random-uniform fields (measured <= 9e-7 abs / 3e-5 rel at
    k=4). On TPU the fused kernel and the stepwise path agree exactly;
    the allclose bound only absorbs the CPU contraction drift (same
    cause as tests/unit/test_sharded.assert_chain_equal)."""
    L = 16
    dtype = jnp.float32
    params = grayscott.Params.from_settings(
        _settings("Pallas", L=L, noise=0.25 if use_noise else 0.0), dtype
    )
    key = jax.random.PRNGKey(31)
    u = jax.random.uniform(key, (L, L, L), dtype)
    v = jax.random.uniform(jax.random.fold_in(key, 1), (L, L, L), dtype)
    seeds = jnp.asarray([9, 17, 5], jnp.int32)

    uk, vk = fused_step(
        u, v, params, seeds, use_noise=use_noise, fuse=fuse
    )
    us, vs = u, v
    for s in range(fuse):
        us, vs = fused_step(
            us, vs, params, seeds.at[2].add(s), use_noise=use_noise,
        )
    np.testing.assert_allclose(
        np.asarray(uk), np.asarray(us), rtol=5e-5, atol=2e-6
    )
    np.testing.assert_allclose(
        np.asarray(vk), np.asarray(vs), rtol=5e-5, atol=2e-6
    )


def test_fuse_steps_down_when_vmem_overflows():
    """When the requested fuse depth overflows the VMEM budget but a
    shallower chain fits, fused_step must step down (keeping the Pallas
    kernel) rather than fall back to XLA — and the trajectory must be
    unchanged."""
    L = 16
    dtype = jnp.float32
    params = grayscott.Params.from_settings(
        _settings("Pallas", L=L, noise=0.25), dtype
    )
    key = jax.random.PRNGKey(41)
    u = jax.random.uniform(key, (L, L, L), dtype)
    v = jax.random.uniform(jax.random.fold_in(key, 1), (L, L, L), dtype)
    seeds = jnp.asarray([2, 4, 8], jnp.int32)

    want_u, want_v = fused_step(
        u, v, params, seeds, use_noise=True, fuse=4
    )

    item = 4
    # Budget that admits fuse=2 at bx=2 but not fuse=4 (bx >= fuse, so
    # fuse=4 needs bx=4 whose input slab alone overflows this budget).
    plane = L * L * item
    budget = (2 * 2 * 6 + 2 * 1 * 4 + 2 * 2 * 2) * plane
    saved = pallas_stencil._VMEM_BUDGET
    pallas_stencil._VMEM_BUDGET = budget
    try:
        assert pallas_stencil.pick_block_planes(L, L, L, item, 4) == 0
        assert pallas_stencil.pick_block_planes(L, L, L, item, 2) > 0
        got_u, got_v = fused_step(
            u, v, params, seeds, use_noise=True, fuse=4
        )
    finally:
        pallas_stencil._VMEM_BUDGET = saved
    # Stepped-down chains (2x fuse=2) lower through different window
    # shapes than one fuse=4 chain; XLA:CPU's shape-sensitive FMA
    # formation drifts a few ulp per stage (see the tolerance note on
    # test_deep_temporal_blocking_matches_single_steps; exact on TPU).
    np.testing.assert_allclose(
        np.asarray(got_u), np.asarray(want_u), rtol=5e-5, atol=2e-6
    )
    np.testing.assert_allclose(
        np.asarray(got_v), np.asarray(want_v), rtol=5e-5, atol=2e-6
    )


def test_bf16_mid_buffers_track_exact_chain(monkeypatch):
    """GS_MID_BF16=1 stores f32 mid-stage buffers as bf16 — an opt-in
    speed/accuracy trade (mid VMEM movement is the kernel's binding
    cost, r3 envelope probe). The approximate chain must track the
    exact one to bf16 mid precision, and the flag must change the
    result (else the A/B measures nothing)."""
    L, k = 16, 4
    dtype = jnp.float32
    params = grayscott.Params.from_settings(
        _settings("Pallas", L=L, noise=0.1), dtype
    )
    key = jax.random.PRNGKey(9)
    u = jax.random.uniform(key, (L, L, L), dtype)
    v = jax.random.uniform(jax.random.fold_in(key, 1), (L, L, L), dtype)
    seeds = jnp.asarray([1, 2, 3], jnp.int32)

    exact_u, exact_v = fused_step(
        u, v, params, seeds, use_noise=True, fuse=k
    )
    monkeypatch.setenv("GS_MID_BF16", "1")
    approx_u, approx_v = fused_step(
        u, v, params, seeds, use_noise=True, fuse=k
    )
    monkeypatch.undo()
    assert not np.array_equal(np.asarray(approx_u), np.asarray(exact_u))
    np.testing.assert_allclose(
        np.asarray(approx_u), np.asarray(exact_u), rtol=0.02, atol=0.02
    )
    np.testing.assert_allclose(
        np.asarray(approx_v), np.asarray(exact_v), rtol=0.02, atol=0.02
    )


def test_max_feasible_fuse_caps_the_v5p16_pod_shape():
    """The dispatch-side chain-depth guard: on the v5p-16 1D pod shape
    (local 64x512x512 f32) the x-chain fits Mosaic's VMEM budget at
    fuse=3 (bx=4) but not 4 or 5 — an uncapped GS_FUSE=5 would silently
    run the XLA fallback every step (advisor finding r3)."""
    saved = pallas_stencil._VMEM_BUDGET
    pallas_stencil._VMEM_BUDGET = pallas_stencil._VMEM_BUDGETS["TPU v5 lite"]
    try:
        assert pallas_stencil.max_feasible_fuse(64, 512, 512, 4, 5) == 3
        # And a shape that fits the requested depth is left alone.
        assert pallas_stencil.max_feasible_fuse(64, 128, 256, 4, 5) == 5
    finally:
        pallas_stencil._VMEM_BUDGET = saved


@pytest.mark.parametrize("nsteps", [1, 3, 7])
def test_pallas_odd_step_counts_match_xla(nsteps):
    """Odd chunk sizes take the fuse pairs + one fuse=rem remainder
    path; the result must not depend on the chunking."""
    a = Simulation(_settings("XLA"), n_devices=1)
    b = Simulation(_settings("Pallas"), n_devices=1)
    a.iterate(nsteps)
    b.iterate(nsteps)
    np.testing.assert_allclose(
        a.get_fields()[0], b.get_fields()[0], rtol=1e-6, atol=5e-7
    )


def test_pallas_faces_kernel_matches_padded_oracle():
    """The with-faces kernel path (face DMAs + in-register edge repair),
    exercised single-device in interpret mode against the XLA
    pad-from-faces oracle — sharded CPU runs take the XLA fallback (the
    interpreter's global state deadlocks under concurrent shard_map
    calls), so this is the off-hardware coverage for that code."""
    L = 32  # bx=16 -> 2 slabs: both x-face DMAs + steady-state pipeline
    dtype = jnp.float32
    params = grayscott.Params.from_settings(_settings("Pallas", L=L), dtype)
    key = jax.random.PRNGKey(3)
    keys = jax.random.split(key, 14)
    u = jax.random.uniform(keys[0], (L, L, L), dtype)
    v = jax.random.uniform(keys[1], (L, L, L), dtype)
    shapes = [(1, L, L)] * 4 + [(L, 1, L)] * 4 + [(L, L, 1)] * 4
    faces = tuple(
        jax.random.uniform(k, s, dtype) for k, s in zip(keys[2:], shapes)
    )
    seeds = jnp.asarray([1, 2, 3], jnp.int32)

    got_u, got_v = fused_step(
        u, v, params, seeds, faces, use_noise=False
    )
    want_u, want_v = xla_fallback(
        u, v, params, seeds, faces, use_noise=False
    )
    np.testing.assert_allclose(
        np.asarray(got_u), np.asarray(want_u), rtol=1e-6, atol=5e-7
    )
    np.testing.assert_allclose(
        np.asarray(got_v), np.asarray(want_v), rtol=1e-6, atol=5e-7
    )


def test_pallas_sharded_multislab():
    """32^3 shards -> bx=16 -> 2 slabs each; CPU takes the XLA fallback
    (kernel-path equivalent is covered by the faces oracle test above)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual CPU devices")
    ref = Simulation(_settings("XLA", L=64), n_devices=8)
    pal = Simulation(_settings("Pallas", L=64), n_devices=8)
    ref.iterate(5)
    pal.iterate(5)
    np.testing.assert_allclose(
        ref.get_fields()[0], pal.get_fields()[0], rtol=1e-6, atol=1e-7
    )


@pytest.mark.parametrize("noise", [0.0, 0.1])
def test_pallas_sharded(noise):
    """Sharded cross-kernel-language equivalence — exact with noise on
    (shared position-keyed stream), plus reproducibility."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual CPU devices")
    ref = Simulation(_settings("XLA", L=16, noise=noise), n_devices=8)
    pal = Simulation(_settings("Pallas", L=16, noise=noise), n_devices=8)
    ref.iterate(10)
    pal.iterate(10)
    np.testing.assert_allclose(
        ref.get_fields()[0], pal.get_fields()[0], rtol=1e-6, atol=1e-7
    )
    if noise:
        pal2 = Simulation(_settings("Pallas", L=16, noise=noise), n_devices=8)
        pal2.iterate(10)
        np.testing.assert_array_equal(
            pal.get_fields()[0], pal2.get_fields()[0]
        )


def test_pallas_sharded_matches_single_device():
    """Sharded Pallas (halo faces) vs single-device Pallas oracle."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual CPU devices")
    one = Simulation(_settings("Pallas", L=16), n_devices=1)
    eight = Simulation(_settings("Pallas", L=16), n_devices=8)
    one.iterate(10)
    eight.iterate(10)
    np.testing.assert_allclose(
        one.get_fields()[0], eight.get_fields()[0], rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        one.get_fields()[1], eight.get_fields()[1], rtol=1e-5, atol=1e-6
    )


def _xchain_inputs(nx=32, ny=16, nz=128, k=3, seed=7):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.random((nx, ny, nz)), jnp.float32)
    v = jnp.asarray(rng.random((nx, ny, nz)), jnp.float32)
    faces = tuple(
        jnp.asarray(rng.random((k, ny, nz)), jnp.float32) for _ in range(4)
    )
    params = grayscott.Params.from_settings(
        _settings("Pallas", L=nx, noise=0.2), jnp.float32
    )
    seeds = jnp.asarray([3, 5, 11], jnp.int32)
    return u, v, faces, params, seeds


@pytest.mark.parametrize("use_noise", [False, True])
def test_x_chain_kernel_matches_fallback(use_noise, monkeypatch):
    """The in-kernel fused x-chain (fuse-wide x faces, the 1D-sharded
    mode) against its XLA fallback: same elementwise program, so the
    tolerance absorbs interpret-kernel vs XLA op-scheduling rounding,
    amplified here by uniform-random fields (gradients far steeper than
    simulation states) across k chained stages — the bitwise guarantees
    are the bv-faces test below and the sharded-vs-single-device test
    (test_sharded.py), both comparing like against like. nx=32 with
    GS_BX=16 exercises the multi-slab face-DMA branches (lo slab, hi
    slab, interior)."""
    nx, ny, nz, k = 32, 16, 128, 3
    u, v, faces, params, seeds = _xchain_inputs(nx, ny, nz, k)
    offs = jnp.asarray([16, 0, 0], jnp.int32)  # interior shard
    row = jnp.int32(64)
    monkeypatch.setenv("GS_BX", "16")  # restores any pre-existing value
    a = fused_step(
        u, v, params, seeds, faces, use_noise=use_noise, fuse=k,
        offsets=offs, row=row,
    )
    monkeypatch.undo()
    b = xchain_fallback(
        u, v, params, seeds, faces, fuse=k, use_noise=use_noise,
        offsets=offs, row=row,
    )
    np.testing.assert_allclose(
        np.asarray(a[0]), np.asarray(b[0]), rtol=1e-4, atol=2e-6
    )
    np.testing.assert_allclose(
        np.asarray(a[1]), np.asarray(b[1]), rtol=1e-4, atol=2e-6
    )


def test_x_chain_with_boundary_faces_equals_no_faces_chain(monkeypatch):
    """A whole-domain block fed frozen-boundary faces must reproduce the
    single-block in-kernel chain BITWISE — the face-DMA ghost source and
    the memset ghost source carry identical values, and the global-
    coordinate mid-stage pinning must degrade exactly to the local
    test. The block is a CUBE spanning the whole global domain (the
    chain mode pins all three axes against the global side ``row``; a
    non-cubic block with an axis longer than row is not a configuration
    the framework constructs). GS_BX=16 keeps the multi-slab face-DMA
    branches covered."""
    nx = ny = nz = 32
    k = 3
    u, v, _, params, seeds = _xchain_inputs(nx, ny, nz, k)
    bv = ((gs_model.U_BOUNDARY,) * 2 + (gs_model.V_BOUNDARY,) * 2)
    faces = tuple(
        jnp.full((k, ny, nz), b, jnp.float32) for b in bv
    )
    offs = jnp.zeros((3,), jnp.int32)
    row = jnp.int32(nx)
    monkeypatch.setenv("GS_BX", "16")
    a = fused_step(
        u, v, params, seeds, faces, use_noise=True, fuse=k,
        offsets=offs, row=row,
    )
    b = fused_step(
        u, v, params, seeds, use_noise=True, fuse=k,
        offsets=offs, row=row,
    )
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


@pytest.mark.parametrize("use_noise", [False, True])
def test_xy_chain_kernel_matches_fallback(use_noise, monkeypatch):
    """The xy-chain mode of the Mosaic kernel body (interpret mode):
    a y-EXTENDED operand — rows covering a y halo below and above the
    interior plus sublane filler, global y origin negative — against
    the XLA xy-chain fallback. Exercises the in-kernel global-y
    mid-stage pinning that lets the chain cross y shard boundaries
    (``temporal.xy_chain`` builds exactly this operand). ny=24 = 8
    interior + 2*3 halo + 2 filler rows at the hi end stays
    sublane-aligned the way the dispatch pads it."""
    nx, k = 32, 3
    ny_int, nz = 8, 128
    ny = ny_int + 2 * k + 2  # interior + halos + alignment filler
    u, v, faces, params, seeds = _xchain_inputs(nx, ny, nz, k)
    # Interior shard in x AND y of a 64^3 global grid: y origin is the
    # block's origin minus the halo depth.
    offs = jnp.asarray([16, 8 - k, 0], jnp.int32)
    row = jnp.int32(64)
    monkeypatch.setenv("GS_BX", "16")  # multi-slab face-DMA branches
    a = fused_step(
        u, v, params, seeds, faces, use_noise=use_noise, fuse=k,
        offsets=offs, row=row,
    )
    monkeypatch.undo()
    b = xchain_fallback(
        u, v, params, seeds, faces, fuse=k, use_noise=use_noise,
        offsets=offs, row=row,
    )
    np.testing.assert_allclose(
        np.asarray(a[0]), np.asarray(b[0]), rtol=1e-4, atol=2e-6
    )
    np.testing.assert_allclose(
        np.asarray(a[1]), np.asarray(b[1]), rtol=1e-4, atol=2e-6
    )


def test_xy_chain_edge_block_pins_out_of_domain_rows(monkeypatch):
    """A global-y-EDGE block's y-extended operand has out-of-domain pad
    rows (gy < 0): the kernel must pin them to the boundary value each
    mid stage — so feeding boundary-constant y-halo content must equal
    the fallback bitwise on the interior rows."""
    nx, k = 16, 2
    ny_int, nz = 12, 128
    ny = ny_int + 2 * k  # 16, already sublane-aligned
    u, v, _, params, seeds = _xchain_inputs(nx, ny, nz, k)
    bv = ((gs_model.U_BOUNDARY,) * 2 + (gs_model.V_BOUNDARY,) * 2)
    faces = tuple(jnp.full((k, ny, nz), b, jnp.float32) for b in bv)
    # y origin -k: rows [0, k) are outside the global domain.
    offs = jnp.asarray([0, -k, 0], jnp.int32)
    row = jnp.int32(64)
    a = fused_step(
        u, v, params, seeds, faces, use_noise=True, fuse=k,
        offsets=offs, row=row,
    )
    b = xchain_fallback(
        u, v, params, seeds, faces, fuse=k, use_noise=True,
        offsets=offs, row=row,
    )
    np.testing.assert_allclose(
        np.asarray(a[0]), np.asarray(b[0]), rtol=1e-4, atol=2e-6
    )


@pytest.mark.parametrize("fuse", [2, 3])
@pytest.mark.parametrize("y0", [0, 16], ids=["y-edge", "y-interior"])
def test_xy_chain_halo_operands_match_padded_operand(fuse, y0, monkeypatch):
    """The xy-chain form — owned fields in place plus a sublane tile of
    y halo per side, assembled into the plane in VMEM — gives owned
    rows BITWISE equal to the same kernel in x-chain mode on the
    y-extended operand built here with pad and concatenate (the form
    the 1D x-chain keeps). Three slabs (GS_BX=16) cover the lo,
    interior and hi DMA branches; a global-y-edge shard has
    out-of-domain lo halo rows (the exchange's boundary constant), an
    interior shard real neighbour rows on both sides."""
    nx, ny, nz, hy = 48, 16, 128, 8
    u, v, _, params, seeds = _xchain_inputs(nx, ny, nz, fuse)
    rng = np.random.default_rng(17)
    bvs = (gs_model.U_BOUNDARY, gs_model.V_BOUNDARY)

    def rows(bv, edge=False):
        if edge:
            return jnp.full((nx, fuse, nz), bv, jnp.float32)
        return jnp.asarray(rng.random((nx, fuse, nz)), jnp.float32)

    def fill(a, bv, lo, hi):
        return jnp.pad(a, ((0, 0), (lo, hi), (0, 0)), constant_values=bv)

    # The exchanged rows next to the owned ones, filler beyond them.
    y_halos = tuple(
        h for bv in bvs
        for h in (fill(rows(bv, edge=y0 == 0), bv, hy - fuse, 0),
                  fill(rows(bv), bv, 0, hy - fuse))
    )
    faces = tuple(
        jnp.asarray(rng.random((fuse, ny + 2 * hy, nz)), jnp.float32)
        for _ in range(4)
    )
    offs = jnp.asarray([16, y0, 0], jnp.int32)
    row = jnp.int32(64)
    monkeypatch.setenv("GS_BX", "16")
    got = fused_step(
        u, v, params, seeds, faces, use_noise=True, fuse=fuse,
        offsets=offs, row=row, y_halos=y_halos, variant="xy_chain",
    )
    padded = tuple(
        jnp.concatenate([y_halos[2 * i], f, y_halos[2 * i + 1]], axis=1)
        for i, f in enumerate((u, v))
    )
    want = fused_step(
        *padded, params, seeds, faces, use_noise=True, fuse=fuse,
        offsets=offs.at[1].add(-hy), row=row,
    )
    for g, w in zip(got, want):
        assert g.shape == (nx, ny, nz)
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(w[:, hy:hy + ny]))


def test_x_chain_rejects_bad_faces():
    u, v, faces, params, seeds = _xchain_inputs(k=3)
    with pytest.raises(ValueError, match="fuse >= 2"):
        fused_step(
            u, v, params, seeds, faces, fuse=1,
        )
    with pytest.raises(ValueError, match="x-chain faces"):
        fused_step(
            u, v, params, seeds, tuple(f[:2] for f in faces), fuse=3,
        )
