"""DMA/compute race check for the pipelined Pallas kernel.

The reference's only race-avoidance mechanism is correct-by-construction
double buffering (``public.jl:67-68``); the fused kernel adds real
concurrency (async DMAs overlapping compute across two buffer slots),
so we run the interpret-mode race detector over a multi-slab
configuration — a subsystem the reference does not have.
"""

import numpy as np
import pytest

from grayscott_jl_tpu.ops import pallas_stencil as _ps


def _gs_spec():
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import kernelgen

    return kernelgen.get_spec(grayscott.MODEL)


def _races_found() -> bool:
    """Whether the interpreter's race detector flagged the last
    interpreted kernel (it prints each race and sets this flag)."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call

    return interpret_pallas_call.races.races_found


def test_pipelined_kernel_has_no_dma_races():
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import pallas_stencil

    # L=80 -> bx=16 -> 5 slabs: prologue, steady state (both slots
    # cycling with outstanding in+out DMAs), epilogue. detect_races is a
    # static jit argument, so this traces its own kernel even if other
    # tests already compiled this shape.
    L = 80
    dtype = jnp.float32
    s = Settings(L=L, Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0, noise=0.1,
                 precision="Float32", backend="CPU", kernel_language="Pallas")
    params = grayscott.Params.from_settings(s, dtype)
    u, v = grayscott.init_fields(L, dtype)
    seeds = jnp.asarray([9, 8, 7], jnp.int32)

    # The detector raises/logs on a race; completing with finite values
    # and matching the XLA oracle means the slot protocol is sound.
    spec = _gs_spec()
    u1, v1 = pallas_stencil.fused_step(
        (u, v), params, seeds, spec=spec, use_noise=False,
        detect_races=True,
    )
    assert not _races_found()
    want_u, want_v = pallas_stencil._xla_fallback(
        (u, v), params, seeds, None, spec=spec, use_noise=False
    )
    np.testing.assert_allclose(
        np.asarray(u1), np.asarray(want_u), rtol=1e-6, atol=5e-7
    )


def _chain_race_case(nx, ny, nz, k, offs, row, seed, monkeypatch,
                     bx=None, y_halo=0):
    """Shared scaffolding for the chain-mode race tests: random fields
    and faces, fused_step under the race detector vs the XLA chain
    fallback, both fields asserted. ``y_halo`` rows of random y halo per
    side give the xy-chain form, whose x faces span the owned rows and
    both halos; the fallback then runs on the assembled plane."""
    import jax
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import pallas_stencil

    dtype = jnp.float32
    s = Settings(L=row, Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0, noise=0.1,
                 precision="Float32", backend="CPU",
                 kernel_language="Pallas")
    params = grayscott.Params.from_settings(s, dtype)
    key = jax.random.PRNGKey(seed)
    u = jax.random.uniform(key, (nx, ny, nz), dtype)
    v = jax.random.uniform(jax.random.fold_in(key, 1), (nx, ny, nz), dtype)
    faces = tuple(
        jax.random.uniform(jax.random.fold_in(key, 2 + i),
                           (k, ny + 2 * y_halo, nz), dtype)
        for i in range(4)
    )
    y_halos = None
    if y_halo:
        y_halos = tuple(
            jax.random.uniform(jax.random.fold_in(key, 6 + i),
                               (nx, y_halo, nz), dtype)
            for i in range(4)
        )
    seeds = jnp.asarray([9, 8, 7], jnp.int32)
    offs = jnp.asarray(offs, jnp.int32)
    row = jnp.int32(row)

    spec = _gs_spec()
    if bx is not None:
        monkeypatch.setenv("GS_BX", str(bx))
    u1, v1 = pallas_stencil.fused_step(
        (u, v), params, seeds, faces, spec=spec, use_noise=True, fuse=k,
        offsets=offs, row=row, detect_races=True, y_halos=y_halos,
    )
    assert not _races_found()
    monkeypatch.undo()
    planes = (u, v)
    if y_halo:
        planes = tuple(
            jnp.concatenate([y_halos[2 * i], f, y_halos[2 * i + 1]], 1)
            for i, f in enumerate(planes)
        )
        offs = offs.at[1].add(-y_halo)
    want_u, want_v = (
        f[:, y_halo:y_halo + ny]
        for f in pallas_stencil._xla_xchain_fallback(
            planes, params, seeds, faces, spec=spec, fuse=k,
            use_noise=True, offsets=offs, row=row,
        )
    )
    np.testing.assert_allclose(
        np.asarray(u1), np.asarray(want_u), rtol=1e-4, atol=2e-6
    )
    np.testing.assert_allclose(
        np.asarray(v1), np.asarray(want_v), rtol=1e-4, atol=2e-6
    )


def test_x_chain_kernel_has_no_dma_races(monkeypatch):
    """The x-chain mode adds fuse-wide face DMAs landing in the ghost
    planes of the slab windows while interior slab DMAs and out-DMAs
    are in flight — run the detector over a multi-slab chain
    (GS_BX=16 -> 3 slabs: lo, interior, hi branches)."""
    _chain_race_case(48, 16, 128, 3, offs=[48, 0, 0], row=144,
                     seed=3, monkeypatch=monkeypatch, bx=16)


def test_xy_chain_kernel_has_no_dma_races(monkeypatch):
    """The xy-chain variant: owned rows DMA'd into the middle of each
    widened VMEM plane, a sublane tile of y halo above and below it
    from the halo operands, and fuse-wide x faces of the widened
    planes, all while the owned rows are written back in place — on a
    GLOBAL-y-EDGE shard (offsets[1] = 0, so the out-of-domain y-pin
    branch executes). The DMAs must stay race-free and match the XLA
    xy-chain fallback."""
    k = 3
    _chain_race_case(32, 8, 128, k, offs=[32, 0, 0], row=64, seed=13,
                     monkeypatch=monkeypatch, bx=16, y_halo=8)


def test_single_buffer_whole_block_slab_has_no_dma_races():
    """Odd nx takes the bx == nx whole-block candidate (r4) with
    single-buffered scratch — the degenerate pipeline (no prefetch
    branch, slot 0 only) must stay race-free and exact on BOTH
    fields."""
    import jax
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.models import grayscott
    from grayscott_jl_tpu.ops import pallas_stencil

    nx = 11  # odd: no power-of-two divisor, whole-block slab
    ny, nz, k = 16, 128, 3
    dtype = jnp.float32
    assert pallas_stencil.pick_block_planes(nx, ny, nz, 4, k) == nx
    s = Settings(L=nx, Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0, noise=0.1,
                 precision="Float32", backend="CPU",
                 kernel_language="Pallas")
    params = grayscott.Params.from_settings(s, dtype)
    key = jax.random.PRNGKey(21)
    u = jax.random.uniform(key, (nx, ny, nz), dtype)
    v = jax.random.uniform(jax.random.fold_in(key, 1), (nx, ny, nz), dtype)
    seeds = jnp.asarray([3, 1, 4], jnp.int32)

    spec = _gs_spec()
    u1, v1 = pallas_stencil.fused_step(
        (u, v), params, seeds, spec=spec, use_noise=True, fuse=k,
        detect_races=True,
    )
    assert not _races_found()
    us, vs = u, v
    for step in range(k):
        us, vs = pallas_stencil._xla_fallback(
            (us, vs), params, seeds.at[2].add(step), None, spec=spec,
            use_noise=True,
        )
    np.testing.assert_allclose(
        np.asarray(u1), np.asarray(us), rtol=1e-4, atol=2e-6
    )
    np.testing.assert_allclose(
        np.asarray(v1), np.asarray(vs), rtol=1e-4, atol=2e-6
    )


def _in_place_case(seed):
    """Random 48x16x128 fields, fuse-3 x faces and params for the
    in-place cases below."""
    import jax
    import jax.numpy as jnp

    from grayscott_jl_tpu.config.settings import Settings
    from grayscott_jl_tpu.models import grayscott

    nx, ny, nz, k = 48, 16, 128, 3
    s = Settings(L=3 * nx, Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0,
                 noise=0.1, precision="Float32", backend="CPU",
                 kernel_language="Pallas")
    params = grayscott.Params.from_settings(s, jnp.float32)
    key = jax.random.PRNGKey(seed)
    fields = tuple(
        jax.random.uniform(jax.random.fold_in(key, i), (nx, ny, nz))
        for i in range(2)
    )
    faces = tuple(
        jax.random.uniform(jax.random.fold_in(key, 2 + i), (k, ny, nz))
        for i in range(4)
    )
    return fields, faces, params, k


@pytest.mark.parametrize("x_chain", [False, True], ids=["chain", "x-chain"])
def test_in_place_slabs_read_before_neighbour_writes(x_chain, monkeypatch):
    """The kernel writes its result over its operand: slab b's output
    DMA covers the last ``fuse`` planes slab b+1's input window reads.
    At 3 slabs and fuse 3 the detector must find each such read ordered
    before the write (the pipeline's wait rule), and the result must
    equal bitwise the whole-block slab, which reads everything before it
    writes anything — and the XLA program within the other tests'
    tolerance (the CPU interpreter and XLA round a few cells 1 ulp
    apart)."""
    import jax.numpy as jnp

    from grayscott_jl_tpu.ops import pallas_stencil

    fields, faces, params, k = _in_place_case(31)
    spec = _gs_spec()
    seeds = jnp.asarray([9, 8, 7], jnp.int32)
    kw = dict(spec=spec, use_noise=True, fuse=k)
    if x_chain:
        kw.update(offsets=jnp.asarray([48, 0, 0], jnp.int32),
                  row=jnp.int32(144))
    else:
        faces = None

    def run(bx, **extra):
        monkeypatch.setenv("GS_BX", str(bx))
        out = pallas_stencil.fused_step(fields, params, seeds, faces,
                                        **kw, **extra)
        monkeypatch.delenv("GS_BX")
        return out

    piped = run(16, detect_races=True)
    assert not _races_found()
    whole = run(48)
    if x_chain:
        want = pallas_stencil._xla_xchain_fallback(
            fields, params, seeds, faces, **kw)
    else:
        want = fields
        for step in range(k):
            want = pallas_stencil._xla_fallback(
                want, params, seeds.at[2].add(step), None, spec=spec,
                use_noise=True,
            )
    for got, same, ref in zip(piped, whole, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-4, atol=2e-6
        )


def test_in_place_kernel_leaves_callers_input_alone(monkeypatch):
    """Called eagerly on arrays the caller still holds (no donation),
    the in-place kernel works on a copy: the caller's fields read the
    same afterwards, and a second call gives the same result."""
    import jax.numpy as jnp

    from grayscott_jl_tpu.ops import pallas_stencil

    fields, _faces, params, k = _in_place_case(37)
    before = [np.asarray(f).copy() for f in fields]
    monkeypatch.setenv("GS_BX", "16")
    seeds = jnp.asarray([1, 2, 3], jnp.int32)
    first = pallas_stencil.fused_step(fields, params, seeds,
                                      spec=_gs_spec(), fuse=k)
    for f, b in zip(fields, before):
        np.testing.assert_array_equal(np.asarray(f), b)
    again = pallas_stencil.fused_step(fields, params, seeds,
                                      spec=_gs_spec(), fuse=k)
    for a, b in zip(first, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(first[0]), before[0])
