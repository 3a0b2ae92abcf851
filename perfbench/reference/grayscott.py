"""Plain reference for the Gray-Scott update, independent of the program.

The semantics are GrayScott.jl's (``src/simulation/Simulation_CPU.jl``
and ``Common.jl``), as the repo's ``tests/oracle.py`` transcribes them:

    u_t = Du * lap(u) - u*v^2 + F*(1-u) + noise * U(-1, 1)
    v_t = Dv * lap(v) + u*v^2 - (F+k)*v

explicit Euler on an ``L^3`` grid with a frozen ghost shell (u=1, v=0),
seeded with u=0.25, v=0.33 on the cube ``[L/2-6, L/2+6]^3``.

The noise is the position-keyed stream documented in the program's
``ops/noise.py`` docstring, re-implemented here from that definition:
each draw is a pure function of (key words, absolute step, global x, y,
z) through the lowbias32 hash, mapped to ``[-1, 1)`` through 23 mantissa
bits. The key words are those of ``jax.random.PRNGKey(seed)`` with
64-bit mode off: ``(0, seed mod 2**32)``.

Every function takes the array namespace ``xp`` (``numpy`` or
``jax.numpy``), so the same arithmetic runs as a float64 NumPy oracle in
the tests and as a ``jax.numpy`` program on the chip (``device.py``),
in the configuration's precision. As every reference module, it
declares ``FIELDS``, ``params``, ``init`` and ``step``. Nothing here
imports ``grayscott_jl_tpu``.
"""

from __future__ import annotations

import numpy as np

SEED_HALF_WIDTH = 6
SEED_U, SEED_V = 0.25, 0.33
U_BOUNDARY, V_BOUNDARY = 1.0, 0.0
#: The fields in the program's order, as the checks name them.
FIELDS = ("u", "v")


def params(settings) -> dict:
    """Du, Dv, F, k from the settings' ``[model]`` table, else their
    flat keys (the program's order of precedence), then dt and noise."""
    table = dict(getattr(settings, "model_params", None) or {})
    out = {k: table.get(k, getattr(settings, k))
           for k in ("Du", "Dv", "F", "k")}
    out.update(dt=settings.dt, noise=settings.noise)
    return out


def key_words(seed: int, x64: bool = False) -> tuple:
    """Raw words of ``jax.random.PRNGKey(seed)``, in 64-bit mode where
    ``x64``."""
    hi = (int(seed) >> 32) % 2**32 if x64 else 0
    return hi, int(seed) % 2**32


def _u32(xp, x):
    return xp.asarray(x, dtype=xp.uint32)


def hash32(xp, x):
    """lowbias32: 32-bit avalanche hash, arithmetic modulo 2**32."""
    x = x ^ (x >> _u32(xp, 16))
    x = x * _u32(xp, 0x7FEB352D)
    x = x ^ (x >> _u32(xp, 15))
    x = x * _u32(xp, 0x846CA68B)
    return x ^ (x >> _u32(xp, 16))


def _bits_to_pm1(xp, bits):
    """uint32 bits -> float32 uniform on [-1, 1): 23 mantissa bits under
    exponent 0 give [1, 2), mapped affinely."""
    f12 = _u32(xp, 0x3F800000) | (bits >> _u32(xp, 9))
    if xp is np:
        f12 = f12.view(np.float32)
    else:
        from jax import lax

        f12 = lax.bitcast_convert_type(f12, xp.float32)
    return f12 * xp.float32(2.0) - xp.float32(3.0)


def unit_noise(xp, key, step, shape, offsets=(0, 0, 0), row=None):
    """float32 U[-1, 1) draws for the block of ``shape`` at global
    ``offsets`` at absolute ``step`` (the step being taken, 0-based).
    ``row`` is the global grid side L."""
    row = shape[2] if row is None else row
    nx, ny, nz = shape
    gx = xp.arange(nx, dtype=xp.uint32).reshape(nx, 1, 1) + _u32(
        xp, offsets[0])
    iy = xp.arange(ny, dtype=xp.uint32).reshape(1, ny, 1) + _u32(
        xp, offsets[1])
    iz = xp.arange(nz, dtype=xp.uint32).reshape(1, 1, nz) + _u32(
        xp, offsets[2])
    k0, k1 = _u32(xp, key[0]), _u32(xp, key[1])
    plane = hash32(
        xp,
        hash32(xp, hash32(xp, k0) ^ k1)
        ^ hash32(xp, hash32(xp, _u32(xp, step)) ^ gx),
    )
    cell = hash32(xp, iy * _u32(xp, row) + iz)
    return _bits_to_pm1(xp, hash32(xp, cell ^ plane))


def init(xp, L: int, dtype):
    """Interior (u, v) at step 0."""
    if L % 2:
        raise ValueError(f"L must be even, got {L}")
    lo, hi = L // 2 - SEED_HALF_WIDTH, L // 2 + SEED_HALF_WIDTH
    idx = xp.arange(L)
    inside = (idx >= lo) & (idx <= hi)
    cube = (inside[:, None, None] & inside[None, :, None]
            & inside[None, None, :])
    u = xp.where(cube, SEED_U, U_BOUNDARY).astype(dtype)
    v = xp.where(cube, SEED_V, V_BOUNDARY).astype(dtype)
    return u, v


def laplacian(xp, f, boundary):
    """7-point Laplacian ``(sum of neighbours - 6*centre) / 6`` with the
    frozen ghost value ``boundary`` outside the domain."""
    p = xp.pad(f, 1, mode="constant", constant_values=boundary)
    return (
        p[:-2, 1:-1, 1:-1] + p[2:, 1:-1, 1:-1]
        + p[1:-1, :-2, 1:-1] + p[1:-1, 2:, 1:-1]
        + p[1:-1, 1:-1, :-2] + p[1:-1, 1:-1, 2:]
        - 6.0 * f
    ) / 6.0


def step(xp, u, v, step_index, params, key, L=None):
    """One explicit-Euler step of the whole grid, in ``u``'s dtype.
    ``params`` maps Du, Dv, F, k, dt, noise to floats."""
    dt_ = u.dtype
    c = {k: xp.asarray(params[k], dtype=dt_) for k in
         ("Du", "Dv", "F", "k", "dt", "noise")}
    uvv = u * v * v
    du = c["Du"] * laplacian(xp, u, U_BOUNDARY) - uvv + c["F"] * (1.0 - u)
    if params["noise"] != 0.0:
        pm1 = unit_noise(xp, key, step_index, u.shape,
                         row=u.shape[2] if L is None else L)
        du = du + c["noise"] * pm1.astype(dt_)
    dv = c["Dv"] * laplacian(xp, v, V_BOUNDARY) + uvv - (c["F"] + c["k"]) * v
    return (u + du * c["dt"]).astype(dt_), (v + dv * c["dt"]).astype(dt_)


def run_numpy(L, nsteps, params, seed, dtype=np.float64, every=None):
    """NumPy oracle: ``nsteps`` steps from step 0. With ``every``,
    returns the list of (step, u, v) after each ``every`` steps;
    otherwise the final (u, v)."""
    key = key_words(seed)
    u, v = init(np, L, dtype)
    out = []
    with np.errstate(over="ignore"):
        for s in range(nsteps):
            u, v = step(np, u, v, s, params, key)
            if every and (s + 1) % every == 0:
                out.append((s + 1, u, v))
    return out if every else (u, v)
