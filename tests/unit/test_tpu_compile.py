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
             face_depth=None, variant=None):
    """Compile ``fuse`` fused steps of the model's generated kernel for
    one described chip; returns the compiled program's HLO text."""
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
    faces = None
    if face_depth is not None:
        face = jax.ShapeDtypeStruct((face_depth,) + shape[1:], dtype,
                                    sharding=sharding)
        faces = (face,) * (2 * spec.n_fields)

    def step(fields, params, seeds, faces):
        return pallas_stencil.fused_step(
            fields, params, seeds, faces, spec=spec, use_noise=True,
            fuse=fuse, variant=variant,
        )

    compiled = jax.jit(step).lower(fields, params, seeds, faces).compile()
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
    y-extended operand (fuse-deep halo rows plus sublane filler, as
    ``parallel/temporal.xy_chain`` builds it) with fuse-wide x faces."""
    fuse = 2
    nx, ny, nz = L // 2, L // 2, L
    ny_p = ny + 2 * fuse
    ny_p += -ny_p % 8
    hlo = _compile(one_chip, "grayscott", jnp.float32, fuse=fuse,
                   field_shape=(nx, ny_p, nz), face_depth=fuse)
    assert 'custom_call_target="tpu_custom_call"' in hlo


def _kernel_metadata(hlo):
    """The JSON object in the Mosaic custom call's kernel_metadata."""
    import json
    import re

    m = re.search(r"kernel_metadata=(\{[^}]*\})", hlo)
    return json.loads(m.group(1))


@pytest.mark.parametrize("variant,face_depth,want", [
    pytest.param(None, None, "chain", id="one-chip-chain"),
    pytest.param(None, 2, "x_chain", id="x-chain"),
    pytest.param("xy_chain", 2, "xy_chain", id="xy-chain"),
])
def test_kernel_launch_is_stamped(one_chip, mosaic, variant, face_depth,
                                  want):
    """Each launch is named ``gs_<variant>`` and carries its variant and
    chain depth in ``kernel_metadata``: what a profiler trace's op text
    shows and the kernel readers of the benchmark key on."""
    fuse = 2
    shape = (L // 2, L // 2 + 8, L) if face_depth else None
    hlo = _compile(one_chip, "grayscott", jnp.float32, fuse=fuse,
                   field_shape=shape, face_depth=face_depth,
                   variant=variant)
    assert _kernel_metadata(hlo) == {"gs_kernel": want, "fuse": str(fuse)}
    assert f"%gs_{want}" in hlo


@pytest.mark.parametrize("face_depth", [
    pytest.param(None, id="one-chip-chain"),
    pytest.param(2, id="x-chain"),
])
def test_chain_loop_updates_fields_in_place(one_chip, mosaic, face_depth):
    """Three fuse-2 launches in a ``lax.fori_loop`` over donated fields,
    as the step runner drives them: the launch aliases each field to
    its output, so the loop carry needs no whole-field copy."""
    from jax import lax

    from grayscott_jl_tpu.obs import xstats

    model = get_model("grayscott")
    spec = kernelgen.get_spec(model)
    settings = Settings(L=L, noise=0.1, precision="Float32",
                        backend="TPU", kernel_language="Pallas")

    def described(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shape = (L // 2, L, L) if face_depth else (L, L, L)
    fields = (described(shape), described(shape))
    params = jax.tree.map(lambda p: described(p.shape, p.dtype),
                          model.make_params(settings, jnp.float32))
    seeds = described((3,), jnp.int32)
    faces = None
    if face_depth:
        faces = (described((face_depth,) + shape[1:]),) * 4

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
