"""The benchmark's plain reference (perfbench/reference) against the repo's
oracle and the program, on the CPU at tiny sizes.

The reference imports nothing of the program; these tests are where the
two meet: the NumPy transcription equals ``tests/oracle.py`` without
noise, its noise equals the program's position-keyed stream bit for
bit, and the program's XLA path agrees with it with noise on, on one
device and on a 2x2x1 mesh of virtual devices. The device reference
runs in the precision it is given, and its float32 trajectory is pinned
bit for bit.
"""

import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from oracle import oracle_run  # noqa: E402  (tests/oracle.py)
from perfbench.reference import grayscott as ref  # noqa: E402
from perfbench.reference.device import Reference  # noqa: E402

PARAMS = dict(Du=0.2, Dv=0.1, F=0.02, k=0.048, dt=1.0, noise=0.1)
SEED = 3_000_000_123  # above 2**31, as the driver's seeds can be


def test_numpy_reference_is_the_oracle_without_noise():
    p = dict(PARAMS, noise=0.0)
    u, v = ref.run_numpy(16, 12, p, seed=0)
    ou, ov = oracle_run(16, np.float64, 12, p["Du"], p["Dv"], p["F"],
                        p["k"], p["dt"])
    np.testing.assert_allclose(u, ou, rtol=0, atol=1e-14)
    np.testing.assert_allclose(v, ov, rtol=0, atol=1e-14)


@pytest.mark.parametrize("offsets,shape,row", [
    ((0, 0, 0), (8, 8, 8), 8),
    ((4, 8, 0), (4, 8, 16), 16),
])
def test_noise_is_the_programs_stream_bitwise(offsets, shape, row):
    from grayscott_jl_tpu.ops.noise import uniform_pm1_block

    key = jax.random.PRNGKey(SEED)
    key_i32 = jax.lax.bitcast_convert_type(key, jnp.int32)
    assert tuple(int(w) for w in np.asarray(key)) == ref.key_words(SEED)
    for step in (0, 7, 401):
        want = np.asarray(uniform_pm1_block(key_i32, step, offsets, shape,
                                            row, jnp.float32))
        with np.errstate(over="ignore"):
            got = ref.unit_noise(np, ref.key_words(SEED), step, shape,
                                 offsets, row)
        np.testing.assert_array_equal(got, want)


def _program_fields(L, nsteps, n_devices):
    from grayscott_jl_tpu import Settings, Simulation

    s = Settings(L=L, precision="Float32", backend="CPU",
                 kernel_language="Plain", **PARAMS)
    sim = Simulation(s, n_devices=n_devices, seed=SEED)
    assert sim.domain.n_blocks == n_devices
    sim.iterate(nsteps)
    return sim.get_fields()


@pytest.mark.parametrize("n_devices", [1, 4])
def test_program_agrees_with_reference_noise_on(n_devices):
    """f32 program against the f64 NumPy reference: 20 noisy steps agree
    to f32 rounding (sharded: the 2x2x1 mesh's faces included)."""
    L, n = 16, 20
    u, v = _program_fields(L, n, n_devices)
    ru, rv = ref.run_numpy(L, n, PARAMS, SEED)
    assert np.max(np.abs(u - ru)) < 2e-6
    assert np.max(np.abs(v - rv)) < 2e-6
    # The noise moves every cell: the comparison is not of a flat field.
    assert np.ptp(u) > 0.05


@pytest.mark.parametrize("mesh,dtype,tol", [
    ((1, 1, 1), "float32", 2e-6), ((2, 2, 1), "float32", 2e-6),
    # The same arithmetic in float64: only the order of rounding differs.
    ((1, 1, 1), "float64", 1e-14), ((2, 2, 1), "float64", 1e-14),
])
def test_device_reference_agrees_with_numpy(mesh, dtype, tol):
    L, n = 16, 20
    r = Reference(ref, L, PARAMS, SEED, jax.devices(), mesh, dtype)
    r.start()
    r.advance(8)
    r.advance(n - 8)
    u, v = (np.asarray(f) for f in r.fields)
    ru, rv = ref.run_numpy(L, n, PARAMS, SEED)
    assert u.dtype == v.dtype == np.dtype(dtype)
    assert np.max(np.abs(u - ru)) < tol
    assert np.max(np.abs(v - rv)) < tol


#: SHA-256 of the float32 reference's u and v bytes after 40 steps at
#: L=16 (PARAMS, SEED), as the reference made them before it took its
#: model and precision as arguments: existing cells compare against the
#: same fields to the last bit.
PINNED_F32 = (
    "5d3e24729a0a14f534a1951e5e685413b2c13b85ebfa7037535a94d127457340",
    "247fc8c8f63161be5bd4c6913302e109cdfcdce7afa4bb97a487276d8520708f",
)


@pytest.mark.parametrize("mesh", [(1, 1, 1), (2, 2, 1)])
def test_float32_reference_is_pinned_bitwise(mesh):
    r = Reference(ref, 16, PARAMS, SEED, jax.devices(), mesh, "float32")
    r.start()
    r.advance(40)
    got = tuple(hashlib.sha256(np.asarray(f).tobytes()).hexdigest()
                for f in r.fields)
    assert got == PINNED_F32


def test_key_words_follow_64_bit_mode():
    """The program's key for a seed past 2**32 differs in 64-bit mode;
    below it the two agree."""
    big = 2**32 + 5
    jax.config.update("jax_enable_x64", False)
    assert ref.key_words(big) == tuple(
        int(w) for w in np.asarray(jax.random.PRNGKey(big)))
    jax.config.update("jax_enable_x64", True)
    for seed in (big, SEED):
        assert ref.key_words(seed, x64=True) == tuple(
            int(w) for w in np.asarray(jax.random.PRNGKey(seed)))
    assert ref.key_words(SEED, x64=True) == ref.key_words(SEED)
