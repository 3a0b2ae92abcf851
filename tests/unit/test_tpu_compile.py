"""Main-path Mosaic kernels compile for a described TPU v5e (no chip).

The TPU compiler is installed with JAX and compiles for a topology that
is described rather than attached, so these tests catch what interpret
mode cannot — slices not aligned to the tiling, VMEM over the limit —
at no chip time. They run at sizes that compile in about two seconds;
the full-size compiles (L=256 at the default fuse) take minutes and are
recorded in CHANGES.md (PR 21) instead.

The topology is described inside a module fixture, never at import:
only one process at a time may load libtpu, and every xdist worker
imports this file. Nothing here runs a kernel or says anything about
its results or speed.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from grayscott_jl_tpu.config.settings import Settings
from grayscott_jl_tpu.models import get_model
from grayscott_jl_tpu.ops import kernelgen, pallas_stencil

L = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the kernel dispatch to the Mosaic path (``interpret=False``)
    and keep these compiles out of the persistent cache, which cannot
    read back an entry compiled for a described chip. 64-bit mode is
    off, as in the f32 runs these kernels serve (an earlier Float64
    test in the same worker turns it on process-wide)."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(sharding, model_name, dtype, *, fuse=1, field_shape=None,
             face_depth=None, variant=None, y_halo=None):
    """Compile ``fuse`` fused steps of the model's generated kernel for
    one described chip; returns the compiled program's HLO text.
    ``y_halo`` rows of y halo per side give the xy-chain form, whose x
    faces span the owned rows and both halos."""
    model = get_model(model_name)
    spec = kernelgen.get_spec(model)
    settings = Settings(L=L, noise=0.1, precision="Float32",
                        backend="TPU", kernel_language="Pallas")
    settings.model = model_name
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=sharding),
        model.make_params(settings, jnp.float32),
    )
    shape = field_shape or (L, L, L)
    fields = tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                   for _ in range(spec.n_fields))
    seeds = jax.ShapeDtypeStruct((3,), jnp.int32, sharding=sharding)
    faces = y_halos = None
    plane = shape[1] + 2 * (y_halo or 0)
    if face_depth is not None:
        face = jax.ShapeDtypeStruct((face_depth, plane, shape[2]), dtype,
                                    sharding=sharding)
        faces = (face,) * (2 * spec.n_fields)
    if y_halo is not None:
        halo = jax.ShapeDtypeStruct((shape[0], y_halo, shape[2]), dtype,
                                    sharding=sharding)
        y_halos = (halo,) * (2 * spec.n_fields)

    def step(fields, params, seeds, faces, y_halos):
        return pallas_stencil.fused_step(
            fields, params, seeds, faces, spec=spec, use_noise=True,
            fuse=fuse, variant=variant, y_halos=y_halos,
        )

    compiled = jax.jit(step).lower(fields, params, seeds, faces,
                                   y_halos).compile()
    return compiled.as_text()


@pytest.mark.parametrize("model_name,dtype,fuse", [
    pytest.param("grayscott", jnp.float32, 1, id="grayscott-f32"),
    pytest.param("grayscott", jnp.bfloat16, 2, id="grayscott-bf16"),
    pytest.param("brusselator", jnp.float32, 1, id="brusselator-f32"),
])
def test_single_block_kernel_compiles(one_chip, mosaic, model_name, dtype,
                                      fuse):
    """The single-chip fused kernel: flagship f32, the bf16 storage
    posture (bf16 fields and mids, f32 compute), and the generated
    kernel of another model."""
    hlo = _compile(one_chip, model_name, dtype, fuse=fuse)
    assert 'custom_call_target="tpu_custom_call"' in hlo


def test_xy_chain_kernel_compiles(one_chip, mosaic):
    """The sharded chain kernel at the 2x2x1 local block of L=128: the
    owned fields in place, a sublane tile of y halo above and below
    (as ``parallel/temporal.xy_chain`` builds them), and fuse-wide x
    faces as tall as the kernel's VMEM plane."""
    fuse = 2
    nx, ny, nz = L // 2, L // 2, L
    hlo = _compile(one_chip, "grayscott", jnp.float32, fuse=fuse,
                   field_shape=(nx, ny, nz), face_depth=fuse,
                   y_halo=pallas_stencil.y_halo_rows(fuse))
    assert 'custom_call_target="tpu_custom_call"' in hlo
    assert "output_to_operand_aliasing" in hlo


def _kernel_metadata(hlo):
    """The JSON object in the Mosaic custom call's kernel_metadata."""
    import json
    import re

    m = re.search(r"kernel_metadata=(\{[^}]*\})", hlo)
    return json.loads(m.group(1))


@pytest.mark.parametrize("variant,face_depth,y_halo,want", [
    pytest.param(None, None, None, "chain", id="one-chip-chain"),
    pytest.param(None, 2, None, "x_chain", id="x-chain"),
    pytest.param("xy_chain", 2, 8, "xy_chain", id="xy-chain"),
])
def test_kernel_launch_is_stamped(one_chip, mosaic, variant, face_depth,
                                  y_halo, want):
    """Each launch is named ``gs_<variant>`` and carries its variant and
    chain depth in ``kernel_metadata``: what a profiler trace's op text
    shows and the kernel readers of the benchmark key on."""
    fuse = 2
    shape = (L // 2, L // 2 + 8, L) if face_depth else None
    if y_halo:
        shape = (L // 2, L // 2, L)
    hlo = _compile(one_chip, "grayscott", jnp.float32, fuse=fuse,
                   field_shape=shape, face_depth=face_depth,
                   variant=variant, y_halo=y_halo)
    assert _kernel_metadata(hlo) == {"gs_kernel": want, "fuse": str(fuse)}
    assert f"%gs_{want}" in hlo


def _xy_chain_rounds_hlo(topo, L=L):
    """HLO of three split-phase xy-chain rounds
    (``parallel/temporal.xy_chain`` with the overlap band cuts) in a
    ``lax.fori_loop`` over donated fields, sharded 2x2x1 at side ``L``."""
    import numpy as np
    from jax import lax, shard_map
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from grayscott_jl_tpu.parallel import temporal

    model = get_model("grayscott")
    spec = kernelgen.get_spec(model)
    settings = Settings(L=L, noise=0.1, precision="Float32",
                        backend="TPU", kernel_language="Pallas")
    names, dims, k = ("x", "y", "z"), (2, 2, 1), 2
    mesh = Mesh(np.array(topo.devices).reshape(dims), names)
    block, rep = P(*names), P()

    def described(shape, dtype, pspec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, pspec))

    fields = (described((L, L, L), jnp.float32, block),) * 2
    params = jax.tree.map(lambda p: described(p.shape, p.dtype, rep),
                          model.make_params(settings, jnp.float32))
    seeds = described((3,), jnp.int32, rep)

    def local(u, v, params, seeds):
        offs = jnp.stack([lax.axis_index(n) * (L // d)
                          for n, d in zip(names, dims)]).astype(jnp.int32)

        def chain_kernel(fields_k, y_halos, faces_k, stp, offs_k):
            return pallas_stencil.fused_step(
                fields_k, params, seeds.at[2].set(stp), faces_k,
                spec=spec, fuse=k, offsets=offs_k, row=L,
                variant="xy_chain", y_halos=y_halos,
            )

        def band_kernel(fields_b, faces_b, stp, offs_b):
            return pallas_stencil._xla_xchain_fallback(
                fields_b, params, seeds.at[2].set(stp), faces_b,
                spec=spec, fuse=k, use_noise=True, offsets=offs_b, row=L,
            )

        def body(i, carry):
            return temporal.xy_chain(
                carry, params, model, depth=k, step=seeds[2] + k * i,
                offs=offs, chain_kernel=chain_kernel, use_noise=True,
                unit_noise=None, row=L, axis_names=names, axis_sizes=dims,
                boundaries=spec.boundaries, overlap=True,
                band_kernel=band_kernel,
            )

        return lax.fori_loop(0, 3, body, (u, v))

    run = shard_map(local, mesh=mesh, in_specs=(block, block, rep, rep),
                    out_specs=(block, block), check_vma=False)
    return jax.jit(run, donate_argnums=(0, 1)).lower(
        *fields, params, seeds).compile().as_text()


@pytest.mark.parametrize("form", [
    pytest.param(None, id="one-chip-chain"),
    pytest.param("x", id="x-chain"),
    pytest.param("xy", id="xy-chain"),
])
def test_chain_loop_updates_fields_in_place(topo, one_chip, mosaic, form):
    """Three fuse-2 launches in a ``lax.fori_loop`` over donated fields,
    as the step runner drives them: the launch aliases each field to
    its output, so the loop carry needs no whole-field copy. The
    xy-chain case runs three whole split-phase rounds on a 2x2x1 mesh,
    whose launch takes the owned blocks and their y halos, so no field
    is padded or sliced either. It runs at L=256: at L=128 the 4 MB
    blocks fit in VMEM, and the compiler keeps them there, with a
    prefetch and an eviction of the carry that a full-size block
    never has."""
    from jax import lax

    from grayscott_jl_tpu.obs import xstats

    if form == "xy":
        hlo = _xy_chain_rounds_hlo(topo, 2 * L)
        assert 'custom_call_target="tpu_custom_call"' in hlo
        assert "output_to_operand_aliasing" in hlo
        assert xstats.field_copies(hlo, 2) == 0
        assert xstats.field_relayouts(hlo, 2) == 0
        return

    model = get_model("grayscott")
    spec = kernelgen.get_spec(model)
    settings = Settings(L=L, noise=0.1, precision="Float32",
                        backend="TPU", kernel_language="Pallas")

    def described(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shape = (L // 2, L, L) if form else (L, L, L)
    fields = (described(shape), described(shape))
    params = jax.tree.map(lambda p: described(p.shape, p.dtype),
                          model.make_params(settings, jnp.float32))
    seeds = described((3,), jnp.int32)
    faces = None
    if form:
        faces = (described((2,) + shape[1:]),) * 4

    def run(fields, params, seeds, faces):
        def body(i, carry):
            return pallas_stencil.fused_step(
                carry, params, seeds.at[2].add(2 * i), faces, spec=spec,
                use_noise=True, fuse=2,
            )

        return lax.fori_loop(0, 3, body, tuple(fields))

    hlo = jax.jit(run, donate_argnums=0).lower(
        fields, params, seeds, faces).compile().as_text()
    assert "output_to_operand_aliasing" in hlo
    assert xstats.field_copies(hlo, 2) == 0
    assert xstats.field_relayouts(hlo, 2) == 0


@pytest.fixture
def float64():
    """64-bit mode on and the persistent cache off (as ``mosaic``)
    for a Float64 XLA compile, restored after."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", True)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_x64", x64)
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("slab", [None, 8], ids=["whole", "slabs"])
def test_blocked_xla_step_loop_holds_no_whole_field(one_chip, float64, slab):
    """Three Float64 XLA steps of a 32x128x128 block in a
    ``lax.fori_loop``, with the blocked step's compiler options: in
    slabs, the loop carries only 8-plane slabs, so no op inside it has
    a whole field (or a whole float32 half of one) as an operand; the
    whole step's loop does. The split and the join run once, outside
    the loop."""
    import re
    from functools import partial

    from grayscott_jl_tpu.ops import noise
    from grayscott_jl_tpu.parallel import schedule

    shape, row = (32, 128, 128), 128
    model = get_model("grayscott")
    settings = Settings(L=row, noise=0.1, precision="Float64",
                        backend="TPU", kernel_language="XLA")

    def described(shp, dtype):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)

    params = jax.tree.map(lambda p: described(p.shape, p.dtype),
                          model.make_params(settings, jnp.float64))

    def run(fields, key, params):
        blk = schedule.Block(
            model=model, params=params, key=key,
            offs=jnp.zeros((3,), jnp.int32), row=row, dims=(1, 1, 1),
            use_noise=True,
            unit_noise=lambda step, offs, shp: noise.uniform_pm1_block(
                key, step, offs, shp, row, jnp.float64),
            pin=tuple, compute_dtype=jnp.float64, overlap=False,
            padded=False, allow_interpret=False,
        )
        out, _ = schedule.run_chain_rounds(
            partial(schedule.xla_step_round if slab is None
                    else schedule.xla_slab_round, blk),
            1, 3, jnp.int32(0),
            fields, slab=slab,
        )
        return out

    hlo = jax.jit(
        run, donate_argnums=0,
        compiler_options=schedule.SLAB_STEP_TPU_COMPILER_OPTIONS,
    ).lower((described(shape, jnp.float64),) * 2,
            described((2,), jnp.int32), params).compile().as_text()
    (body,) = re.findall(r"body=%([\w.\-]+)", hlo)
    loop = re.search(r"^%" + re.escape(body) + r" .*?^}", hlo,
                     re.M | re.S).group(0)
    assert ("[32,128,128]" in loop) == (slab is None)
