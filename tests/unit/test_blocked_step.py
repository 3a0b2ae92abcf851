"""The one-chip XLA step run slab by slab in x (``parallel/schedule``:
``step_slab_depth``, ``split_slabs``, ``xla_slab_round``).

A one-chip block of more than two slabs' cells steps each x-slab on
its own, with the neighbouring slabs' facing planes as its x ghosts.
The arithmetic per cell is the whole block's, so on XLA:CPU the
slabbed trajectories equal the whole-block ones bitwise, which these
tests require wherever XLA:CPU gives it (one case, stated there, may
differ by one ulp). The slab budget is lowered through the slab
function's own ``budget`` argument, so that 16^3 blocks run in slabs
here."""

import sys
import pathlib
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from grayscott_jl_tpu.config.settings import Settings
from grayscott_jl_tpu.models import get_model
from grayscott_jl_tpu.ops import noise as noise_ops
from grayscott_jl_tpu.parallel import schedule
from grayscott_jl_tpu.simulation import Simulation

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from oracle import oracle_run  # noqa: E402

PARAMS = dict(Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0)
DTYPES = {"Float32": jnp.float32, "Float64": jnp.float64}


@pytest.fixture
def x64():
    """64-bit mode for the Float64 cases (restored after)."""
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


def _steps(model_name, precision, noise, shape, nsteps, slab):
    """``nsteps`` XLA steps of ``model_name`` on a block of ``shape``
    from seeded random fields, whole (``slab`` None) or in x-slabs of
    ``slab`` planes."""
    dtype = DTYPES[precision]
    model = get_model(model_name)
    settings = Settings(L=shape[1], noise=noise, precision=precision,
                        backend="CPU", **PARAMS)
    settings.model = model_name
    params = model.make_params(settings, dtype)
    key = jnp.array([1234, 5678], jnp.int32)
    rng = np.random.default_rng(11)
    fields = tuple(jnp.asarray(rng.uniform(0.0, 1.0, shape), dtype)
                   for _ in range(model.n_fields))

    def unit_noise(step, offsets, shp):
        return noise_ops.uniform_pm1_block(key, step, offsets, shp,
                                           shape[1], dtype)

    blk = schedule.Block(
        model=model, params=params, key=key,
        offs=jnp.zeros((3,), jnp.int32), row=shape[1], dims=(1, 1, 1),
        use_noise=noise > 0, unit_noise=unit_noise, pin=tuple,
        compute_dtype=dtype, overlap=False, padded=False,
        allow_interpret=False,
    )

    round_fn = (schedule.xla_step_round if slab is None
                else schedule.xla_slab_round)

    @jax.jit
    def run(fields):
        out, split = schedule.run_chain_rounds(
            partial(round_fn, blk), 1, nsteps,
            jnp.int32(3), fields, slab=slab,
        )
        assert not split
        return out

    return [np.asarray(f) for f in run(fields)]


@pytest.mark.parametrize("precision", ["Float64", "Float32"])
@pytest.mark.parametrize("noise", [0.0, 0.1], ids=["noiseless", "noise"])
@pytest.mark.parametrize("model_name,shape,slab", [
    pytest.param("grayscott", (16, 16, 16), 4, id="grayscott-divides"),
    pytest.param("grayscott", (18, 16, 16), 4, id="grayscott-remainder"),
    pytest.param("heat", (16, 16, 16), 5, id="heat-remainder"),
    pytest.param("brusselator", (16, 16, 16), 1, id="brusselator-planes"),
])
def test_slab_step_agrees_with_the_whole_step(x64, model_name, precision,
                                             noise, shape, slab):
    """Four steps in slabs against four whole-block steps: every
    registered field count (heat one, the others two), both
    precisions, noise on and off, and an x extent the depth does not
    divide (last slab shallower). Bitwise, except heat with noise
    (``D * lap + noise``): there XLA:CPU compiles the two four-step
    loops' arithmetic differently (one step alone is bitwise), so the
    fields may differ by one ulp of their scale, ``eps * max|T|``, and
    no more."""
    whole = _steps(model_name, precision, noise, shape, 4, None)
    slabbed = _steps(model_name, precision, noise, shape, 4, slab)
    for a, b in zip(whole, slabbed):
        if model_name == "heat" and noise:
            tol = np.finfo(a.dtype).eps * np.abs(a).max()
            np.testing.assert_allclose(b, a, rtol=0, atol=tol)
        else:
            np.testing.assert_array_equal(a, b)
    start = _steps(model_name, precision, noise, shape, 0, None)
    assert not np.array_equal(whole[-1], start[-1])


def test_split_and_join_slabs_round_trip():
    f = jnp.arange(7 * 3 * 2, dtype=jnp.float32).reshape(7, 3, 2)
    (slabs,) = schedule.split_slabs((f,), 3)
    assert [s.shape[0] for s in slabs] == [3, 3, 1]
    (back,) = schedule.join_slabs((slabs,))
    np.testing.assert_array_equal(back, f)


def test_slab_noise_draw_is_the_whole_field_draw():
    """The position-keyed draw of a slab at x offset ``x0`` is the
    whole-field draw at the same cells, bitwise: the slabbed step's
    noise is the whole step's."""
    key = jnp.array([99, -7], jnp.int32)
    L = 16
    whole = noise_ops.uniform_pm1_block(key, 5, (0, 0, 0), (L, L, L), L,
                                        jnp.float32)
    for x0, bx in ((0, 4), (4, 4), (12, 4), (15, 1), (10, 3)):
        slab = noise_ops.uniform_pm1_block(
            key, 5, jnp.array([x0, 0, 0], jnp.int32), (bx, L, L), L,
            jnp.float32,
        )
        np.testing.assert_array_equal(slab, whole[x0:x0 + bx])


def _run(monkeypatch, precision, noise, budget=None, nsteps=10):
    """``nsteps`` steps of a 16^3 ``Simulation``, its XLA step in slabs
    of ``budget`` cells where given; returns the runner's slab count
    and the fields."""
    with monkeypatch.context() as m:
        if budget is not None:
            m.setattr(schedule, "step_slab_depth",
                      partial(schedule.step_slab_depth, budget=budget))
        s = Settings(L=16, steps=nsteps, noise=noise, precision=precision,
                     backend="CPU", kernel_language="Plain", **PARAMS)
        sim = Simulation(s, n_devices=1, seed=5)
        slabs = sim.step_slabs(nsteps)
        sim.iterate(nsteps)
        return slabs, sim.get_fields()


@pytest.mark.parametrize("precision,rtol", [("Float32", 2e-5),
                                            ("Float64", 1e-12)])
def test_ten_steps_through_simulation(monkeypatch, precision, rtol):
    """Ten steps of a ``Simulation`` whose step runs in slabs (budget
    lowered to 3 planes of 16x16: six slabs, the last one plane): the
    whole-block runner's fields, bitwise, and the NumPy oracle's at the
    tolerances of ``test_model``."""
    n_whole, whole = _run(monkeypatch, precision, 0.0)
    n_slabs, slabbed = _run(monkeypatch, precision, 0.0, budget=3 * 16 * 16)
    assert (n_whole, n_slabs) == (1, 6)
    for a, b in zip(whole, slabbed):
        np.testing.assert_array_equal(a, b)
    ou, ov = oracle_run(16, np.dtype(DTYPES[precision]).type, 10,
                        **PARAMS)
    u, v = slabbed
    np.testing.assert_allclose(u, ou, rtol=rtol, atol=rtol)
    np.testing.assert_allclose(v, ov, rtol=rtol, atol=rtol)


def test_ten_noisy_steps_through_simulation_bitwise(monkeypatch):
    """The same with noise on, in Float64: bitwise."""
    _, whole = _run(monkeypatch, "Float64", 0.1)
    n_slabs, slabbed = _run(monkeypatch, "Float64", 0.1, budget=2 * 16 * 16)
    assert n_slabs == 8
    for a, b in zip(whole, slabbed):
        np.testing.assert_array_equal(a, b)
