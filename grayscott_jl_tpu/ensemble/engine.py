"""Batched ensemble engine: N scenarios of one model in ONE executable.

A parameter sweep (e.g. the Gray-Scott phase diagram over F/k/Du/Dv,
or a Brusselator A/B sweep — members parametrize the run's registered
model) used to cost N full launches; here the N parameter sets run as one compiled program:
:class:`EnsembleSimulation` stacks a leading **member** axis onto the
fields, params, and PRNG keys, and ``vmap``-s the *unchanged* per-member
step body (``Simulation._local_run`` and the chain schedules of
``parallel/schedule.py`` it drives) over it — stencil, in-jit noise,
temporal-blocking chains, and the ``lax.ppermute`` halo exchange all
batch through JAX's collective batching rules with zero ensemble-aware
code in ``ops/`` or ``parallel/``. That is the point: the member axis
composes with the existing spatial sharding instead of forking it.

Mesh: the member axis is optionally sharded on a ``member`` ('m') mesh
dimension in FRONT of the spatial axes — ``member_shards = m`` builds a
``(m, dx, dy, dz)`` mesh where each device group of ``dx*dy*dz`` chips
holds ``N/m`` members, and halo ppermutes still ride the spatial axes
only (members are independent; no member-axis collectives exist at
all).

Equality contract (asserted in tier-1, ``tests/unit/test_ensemble.py``):
member ``k`` of an N-member run is **bitwise identical** to a solo
:class:`~..simulation.Simulation` with member ``k``'s params and seed
on the same spatial mesh. Everything downstream leans on this — the
per-member output stores (``ensemble/io.py``) are byte-identical to
solo stores, so ensemble restart/resume and the chaos byte-identity
harness reuse the solo machinery unchanged.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from ..config.settings import Settings
from ..parallel.domain import CartDomain
from ..simulation import (
    AXIS_NAMES,
    FieldSnapshot,
    Simulation,
    mesh_for_topology,
)
from . import spec as ensemble_spec

#: Mesh-axis name of the member dimension (in front of the spatial
#: ('x', 'y', 'z') axes).
MEMBER_AXIS = "m"


class EnsembleFieldSnapshot(FieldSnapshot):
    """A member-stacked snapshot: blocks carry a leading member axis
    and the health probe resolves per member."""

    #: Per-slot activity mask stamped by
    #: :meth:`EnsembleSimulation.snapshot_async`; None = every slot is
    #: a real member. Idle pack slots (docs/SERVICE.md) are excluded
    #: from health/numerics aggregation but still resolve per index.
    member_active = None

    def health_report(self):
        """Per-member :class:`~..resilience.health.EnsembleHealthReport`
        (or None) — each member's fused isfinite+range probe, so one
        diverging member is attributed by index instead of anonymously
        aborting the whole sweep. Idle pack slots are masked out of the
        aggregate verdict and the bad-member attribution."""
        if self._health is None:
            return None
        from ..resilience.health import EnsembleHealthReport, HealthReport

        finite, *minmax = (np.asarray(x) for x in self._health)
        return EnsembleHealthReport(tuple(
            HealthReport(
                bool(finite[i]),
                *(float(m[i]) for m in minmax),
                names=self.field_names,
            )
            for i in range(finite.shape[0])
        ), active=self.member_active)

    def numerics_report(self):
        """Per-member numerics statistics aggregated into one
        :class:`~..obs.numerics.NumericsReport` (``members`` carries
        the per-member rows; ``fields`` the cross-member aggregate over
        the ACTIVE slots) — the same attribution shape as the
        per-member health probe."""
        if self._numerics is None:
            return None
        from ..obs import numerics as obs_numerics

        vals = [np.asarray(x) for x in self._numerics]
        members = [
            obs_numerics.resolve_report(
                [v[i] for v in vals], self.field_names
            ).fields
            for i in range(vals[0].shape[0])
        ]
        return obs_numerics.NumericsReport.aggregate_members(
            members, active=self.member_active
        )

    def checksum_report(self):
        """Per-member device checksums ``[{field: int}, ...]`` (the
        vmapped integrity probe resolves one value per member per
        field) — the ensemble writers route member ``k``'s record to
        member ``k``'s store, keeping member stores byte-identical to
        solo stores."""
        if self._checksums is None:
            return None
        vals = [np.asarray(c) for c in self._checksums]
        return [
            {n: int(v[i]) for n, v in zip(self.field_names, vals)}
            for i in range(vals[0].shape[0])
        ]

    def _verify_checksums(self, host_parts) -> None:
        """Member-resolved verification: the device checksum of each
        member slice is recomputed from that member's landed host
        bytes, so silent write-path corruption is attributed by member
        index — the same attribution shape as the health probe."""
        from ..resilience.integrity import (
            CorruptionError,
            host_field_checksum,
        )

        vals = [np.asarray(c) for c in self._checksums]
        n = vals[0].shape[0]
        totals = [[0] * n for _ in self.field_names]
        for part in host_parts:
            m_off = part[0][0]
            for fi, arr in enumerate(part[2:]):
                for j in range(arr.shape[0]):
                    totals[fi][m_off + j] = (
                        totals[fi][m_off + j]
                        + host_field_checksum(arr[j])
                    ) % (1 << 32)
        for fi, name in enumerate(self.field_names):
            for i in range(n):
                want, got = int(vals[fi][i]), totals[fi][i]
                if want != got:
                    raise CorruptionError(
                        "device-side field checksum mismatch: device "
                        f"{want:#010x}, host {got:#010x} — snapshot "
                        "bytes were silently corrupted in flight",
                        step=self.step, var=name, member=i,
                    )


def member_blocks(blocks, member: int, member_offset: int = 0):
    """Extract one member's spatial ``(offsets, sizes, *fields)``
    blocks from member-stacked 4D snapshot blocks.

    Each 4D entry covers a member range ``[off_m, off_m + n_m)``; the
    entry contributes iff it holds ``member``. Returns solo-format 3D
    blocks — exactly what a solo run's ``local_blocks()`` yields, which
    is what keeps per-member stores byte-identical to solo stores.
    """
    out = []
    for offsets, sizes, *fblocks in blocks:
        off_m, n_m = offsets[0], sizes[0]
        if not (off_m <= member < off_m + n_m):
            continue
        i = member - off_m
        out.append(
            (tuple(offsets[1:]), tuple(sizes[1:]))
            + tuple(fb[i] for fb in fblocks)
        )
    return out


class EnsembleSimulation(Simulation):
    """N independent parameter sets advancing in one compiled launch."""

    snapshot_cls = EnsembleFieldSnapshot
    is_ensemble = True

    def __init__(
        self,
        settings: Settings,
        *,
        n_devices: Optional[int] = None,
        seed: int = 0,
        mesh_dims: Optional[Tuple[int, int, int]] = None,
    ):
        ens = getattr(settings, "ensemble", None)
        if ens is None:
            raise ValueError(
                "EnsembleSimulation requires settings.ensemble "
                "(an [ensemble] TOML table; docs/ENSEMBLE.md)"
            )
        self.ens: ensemble_spec.EnsembleSettings = ens
        self.n_members = ens.n
        self.member_shards = int(ens.member_shards)
        self.member_seeds = ensemble_spec.resolve_seeds(ens, seed)
        #: Per-slot activity mask (None = all real): idle pack slots
        #: (docs/SERVICE.md) advance inside the same compiled program
        #: but write no stores and never pollute health attribution or
        #: the aggregate cell-updates/s.
        self.member_active = (
            None if all(ens.active) else tuple(ens.active)
        )
        super().__init__(
            settings, n_devices=n_devices, seed=seed,
            mesh_dims=mesh_dims,
        )

    @property
    def active_member_count(self) -> int:
        """Real (non-idle) members — the count aggregate throughput
        and the driver's completion line are scaled by."""
        return self.ens.active_n

    # ------------------------------------------------- construction hooks

    def _make_domain(self, devices) -> CartDomain:
        m = self.member_shards
        if len(devices) % m:
            raise ValueError(
                f"member_shards = {m} does not divide the "
                f"{len(devices)} selected devices"
            )
        # The member axis consumes its devices in front; the spatial
        # decomposition (and therefore `self.sharded`, the halo
        # exchange, kernel dispatch, autotune mesh sweeps) sees only
        # the remaining count — unchanged solo semantics underneath.
        return CartDomain.create(
            len(devices) // m, self.settings.L,
            dims=self._mesh_dims_override,
        )

    def _make_params(self):
        """Member-stacked Params pytree of the run's model: every leaf
        is ``(N,)``, fed to the vmapped step body with ``in_axes=0``.
        Params live at the COMPUTE dtype, like the solo path
        (docs/PRECISION.md — f32 under the ``bf16_f32acc`` posture)."""
        return self.model.params_cls(*(
            jnp.asarray([mem.value(f) for mem in self.ens.members],
                        self.compute_dtype)
            for f in self.model.params_cls._fields
        ))

    def _resolve_use_noise(self) -> bool:
        # One compiled program for all members: the noise term is
        # traced in if ANY member draws (a member with noise = 0 then
        # adds an exact-zero field — see docs/ENSEMBLE.md for the
        # equality fine print).
        return any(mem.value("noise") != 0.0 for mem in self.ens.members)

    def _make_base_key(self, seed: int):
        """(N, 2) stacked PRNG keys — per-member position-keyed noise
        streams; member k's stream equals a solo run at its seed."""
        return jnp.stack([
            jax.random.PRNGKey(s) for s in self.member_seeds
        ])

    def _tune_extras(self) -> dict:
        return {
            "ensemble": self.n_members,
            "member_shards": self.member_shards,
            "sim_cls": type(self),
        }

    def _apply_tune_extras(self, decision) -> None:
        """Adopt a measured ``member_shards`` split (the batch-size ×
        block-shape trade-off axis) before the mesh is built."""
        m = getattr(decision, "member_shards", None)
        if m is None or int(m) == self.member_shards:
            return
        m = int(m)
        if self.n_members % m or self.domain.n_blocks * self.member_shards % m:
            return  # infeasible for this run's device/member counts
        total = self.domain.n_blocks * self.member_shards
        self.member_shards = m
        self.domain = CartDomain.create(total // m, self.settings.L)
        self.sharded = self.domain.n_blocks > 1
        decision.provenance["adopted_member_shards"] = m

    def _build_mesh(self, devices, backend: str) -> None:
        m = self.member_shards
        if m == 1 and not self.sharded:
            self.mesh = None
            self.field_sharding = None
            self.device = devices[0]
            return
        shape = (m,) + self.domain.dims
        self.mesh = Mesh(
            mesh_for_topology(shape, devices, backend),
            (MEMBER_AXIS,) + AXIS_NAMES,
        )
        self.field_sharding = NamedSharding(
            self.mesh, P(MEMBER_AXIS, *AXIS_NAMES)
        )

    def _probe_fn(self):
        from ..resilience.health import device_probe

        return jax.vmap(device_probe)

    def _numerics_probe_fn(self):
        """Numerics reductions vmapped over the member axis — each
        member's statistics resolve individually
        (``EnsembleFieldSnapshot.numerics_report``), so a drifting
        member of a sweep is attributed by index, mirroring the
        per-member health probe."""
        from ..obs.numerics import device_numerics_probe

        return jax.vmap(device_numerics_probe)

    def _resolve_numerics_host(self, raw):
        from ..obs import numerics as obs_numerics

        vals = [np.asarray(x) for x in raw]
        members = [
            obs_numerics.resolve_report(
                [v[i] for v in vals], self.model.field_names
            ).fields
            for i in range(vals[0].shape[0])
        ]
        return obs_numerics.NumericsReport.aggregate_members(
            members, active=self.member_active
        )

    def _checksum_probe_fn(self):
        """Integrity checksums vmapped over the member axis — one
        wrapped word sum per member per field, so corruption detection
        attributes the bad member by index."""
        from ..resilience.integrity import device_field_checksum

        return jax.vmap(device_field_checksum)

    def _apply_snapshot_bitflip(self, copies, field="u"):
        """Member-addressable ``bitflip``: corrupt ONE member's slice
        of the snapshot copy (member from ``GS_FAULT_MEMBER``, like
        ``poison_nan``) — detection must name this member while the
        other members' boundary bytes verify clean."""
        from ..config.env import env_int
        from ..resilience.integrity import apply_bitflip

        member = env_int("GS_FAULT_MEMBER", 0) % self.n_members
        i = self._field_index(field if field is not True else "u")
        flipped = apply_bitflip(copies[i], (member, 0, 0, 0))
        return copies[:i] + (flipped,) + copies[i + 1:]

    def snapshot_async(self, **kw):
        """Member-stacked snapshot with the activity mask stamped on,
        so the health/numerics resolution downstream (async writer
        thread, health guard) knows which slots are real members."""
        snap = super().snapshot_async(**kw)
        snap.member_active = self.member_active
        return snap

    # ------------------------------------------------------------ fields

    def _init_fields(self):
        """Member-stacked initial fields ``(N, *grid)``.

        The model's seed pattern is parameter-independent (it only
        depends on L), so every member starts from the same block —
        broadcast, not recomputed N times.
        """
        L, dtype, N = self.settings.L, self.dtype, self.n_members
        if self.mesh is None:
            return tuple(
                jax.device_put(
                    jnp.broadcast_to(f, (N,) + f.shape), self.device
                )
                for f in self.model.init(L, dtype)
            )

        dom = self.domain
        gshape = (N,) + dom.storage_shape

        def make(field_idx: int):
            def cb(index):
                m_sl, sp = index[0], index[1:]
                offsets = tuple(s.start or 0 for s in sp)
                sizes = tuple(
                    (s.stop or g) - (s.start or 0)
                    for s, g in zip(sp, dom.storage_shape)
                )
                blk = self.model.init(
                    L, dtype, offsets=offsets, sizes=sizes
                )[field_idx]
                n_m = (m_sl.stop or N) - (m_sl.start or 0)
                return jnp.broadcast_to(blk, (n_m,) + blk.shape)

            return jax.make_array_from_callback(
                gshape, self.field_sharding, cb
            )

        return tuple(make(i) for i in range(self.model.n_fields))

    # ------------------------------------------------------------ runner

    def _make_step_fn(self, nsteps: int, mesh=None):
        """The un-jitted ``nsteps``-step ensemble advance (see the base
        class: shared by the donating live runner and the non-donating
        SDC replay, optionally on a permuted ``mesh``).

        ``vmap`` of the per-member body over the leading axis; under a
        mesh, ``shard_map`` wraps the vmapped body with the member axis
        sharded on 'm' and the spatial axes exactly as solo — halo
        ppermutes batch through vmap's collective batching rules, so
        every per-member value (noise draws included) is computed by
        the same program a solo run compiles.
        """
        local = partial(self._local_run, nsteps=nsteps)
        nf = self.model.n_fields
        member_local = jax.vmap(
            local, in_axes=(0,) * nf + (0, None, 0)
        )
        if self.mesh is not None:
            fspec = P(MEMBER_AXIS, *AXIS_NAMES)
            mspec = P(MEMBER_AXIS)  # keys (N, 2) / params leaves (N,)
            return shard_map(
                member_local,
                mesh=self.mesh if mesh is None else mesh,
                in_specs=(fspec,) * nf + (mspec, P(), mspec),
                out_specs=(fspec,) * nf,
                check_vma=False,
            )
        return member_local

    def _replay_arg_shardings(self, mesh):
        """(base_key, params) ride the member axis: both are
        member-stacked inputs sharded on 'm' (see ``_make_step_fn``'s
        in_specs), so a shadow replay must place them on the permuted
        mesh the same way."""
        ms = NamedSharding(mesh, P(MEMBER_AXIS))
        return ms, ms

    # ------------------------------------------------------------ output

    def _shard_parts(self, *arrays):
        """4D per-shard parts: offsets/sizes carry the member range in
        front of the spatial box; only the spatial dims are clipped to
        the true domain."""
        L = self.settings.L
        first = arrays[0]

        def box(index):
            idx = index if isinstance(index, tuple) else (index,)
            offsets = tuple(sl.start or 0 for sl in idx)
            sizes = tuple(
                (sl.stop or g) - (sl.start or 0)
                for sl, g in zip(idx, first.shape)
            )
            return offsets, sizes

        other_shards = [
            {box(s.index): s for s in a.addressable_shards}
            for a in arrays[1:]
        ]
        parts = []
        for sh in first.addressable_shards:
            offsets, sizes = box(sh.index)
            true = (sizes[0],) + tuple(
                min(L - o, s) for o, s in zip(offsets[1:], sizes[1:])
            )
            parts.append(
                (offsets, true, sh.data)
                + tuple(m[(offsets, sizes)].data for m in other_shards)
            )
        return parts

    def metrics_labels(self) -> dict:
        """Solo labels plus the member count: an 8-member batched
        launch and a solo run of the same model/mesh must not share a
        step-latency histogram — the batched step does N members of
        work per sample (``obs/metrics.py``)."""
        return {**super().metrics_labels(),
                "members": str(self.n_members)}

    def get_fields(self):
        """Host ``(N, L, L, L)`` copies of the model's fields, storage
        pad stripped."""
        jax.block_until_ready(self.fields)
        L = self.settings.L
        return tuple(
            np.asarray(f)[:, :L, :L, :L] for f in self.fields
        )

    def member_fields(self, member: int):
        """Host fields of one member — the solo ``get_fields`` shape."""
        return tuple(f[member] for f in self.get_fields())

    def poison_nan(self, field="u", member: Optional[int] = None
                   ) -> None:
        """Chaos hook: poison ONE member's field (default from
        ``GS_FAULT_MEMBER``, else member 0) — the per-member health
        attribution scenario: the guard must name this member, and the
        other members' trajectories must stay untouched."""
        from ..config.env import env_int

        if member is None:
            member = env_int("GS_FAULT_MEMBER", 0)
        member %= self.n_members
        i = self._field_index(field)
        arr = self.fields[i]
        poisoned = arr.at[(member,) + (0,) * (arr.ndim - 1)].set(
            jnp.asarray(float("nan"), arr.dtype)
        )
        self.fields = (
            self.fields[:i] + (poisoned,) + self.fields[i + 1:]
        )

    def _sdc_site(self, arr, device=None):
        """Member-addressable ``sdc`` poison site: the spatial center
        of the target device's shard, with the member coordinate pinned
        from ``GS_FAULT_MEMBER`` when set. Under ``member_shards > 1``
        pinning the member can move the cell into ANOTHER device's
        member-block — the owning device is re-resolved so the
        injection record (and the attribution the test asserts) names
        the device that actually holds the poisoned cell."""
        from ..config.env import env_int

        name, index = super()._sdc_site(arr, device)
        member = env_int("GS_FAULT_MEMBER", -1)
        if member >= 0:
            index = (member % self.n_members,) + index[1:]
            for sh in arr.addressable_shards:
                idx = (sh.index if isinstance(sh.index, tuple)
                       else (sh.index,))
                if all(
                    (sl.start or 0) <= c < (
                        g if sl.stop is None else sl.stop)
                    for sl, c, g in zip(idx, index, arr.shape)
                ):
                    d = sh.device
                    name = f"{d.platform}:{d.id}"
                    break
        return name, index

    # ------------------------------------------------------------ repack

    def repack(self, settings: Settings, *, seed: int = 0) -> None:
        """Rebind this (already-compiled) ensemble to a NEW member set
        — the warm-launch seam the serve scheduler packs requests onto
        (docs/SERVICE.md).

        Member parameters, PRNG keys, and seeds are runtime *inputs* of
        the compiled step program (``_make_params`` stacks them as
        arrays the jitted runner takes as arguments), so a batch with
        the same shape signature — member count, member_shards, model,
        L, precision, halo/overlap schedule, and noise tracing — reuses
        every cached executable in ``self._runners`` with zero
        recompilation. Anything that would change the traced program is
        refused loudly; the caller (``serve/worker.py``) keys its warm
        cache so that never happens in practice.
        """
        ens = getattr(settings, "ensemble", None)
        if ens is None:
            raise ValueError("repack needs settings.ensemble")
        if ens.n != self.n_members or int(ens.member_shards) != (
            self.member_shards
        ):
            raise ValueError(
                f"repack shape mismatch: compiled for "
                f"{self.n_members} members x {self.member_shards} "
                f"shards, got {ens.n} x {ens.member_shards}"
            )
        if ens.model != self.ens.model:
            raise ValueError(
                f"repack model mismatch: compiled for "
                f"{self.ens.model!r}, got {ens.model!r}"
            )
        if settings.L != self.settings.L:
            raise ValueError(
                f"repack L mismatch: compiled for L={self.settings.L}, "
                f"got L={settings.L}"
            )
        old_ens = self.ens
        self.ens = ens
        if self._resolve_use_noise() != self.use_noise:
            self.ens = old_ens
            raise ValueError(
                "repack noise-tracing mismatch: the compiled program "
                f"{'draws' if self.use_noise else 'draws no'} noise; "
                "pack batches keyed by noise as serve/scheduler does"
            )
        self.settings = settings
        self.n_members = ens.n
        self.member_seeds = ensemble_spec.resolve_seeds(ens, seed)
        self.member_active = (
            None if all(ens.active) else tuple(ens.active)
        )
        self.params = self._make_params()
        self.base_key = self._make_base_key(seed)
        self.fields = self._init_fields()
        self.step = 0
        # Per-launch provenance: a previous batch's elastic-restore
        # plan must not leak into the next batch's RunStats.
        self.reshard = None

    # ----------------------------------------------------------- restore

    def member_init_fields(self):
        """Host initial fields of ONE member — what an elastic GROW
        initializes new trailing members from (docs/RESHARD.md).

        The model's init is parameter-independent by declaration
        contract (it depends on L only), so a grown member's state at
        the resume step is exactly the block a fresh member would have
        started from; its trajectory from there equals a solo run of
        its params/seed whose integration *begins* at the resume step
        (the noise stream is keyed on absolute step, so joining late
        does not alias any other member's draws).
        """
        return tuple(
            np.asarray(f)
            for f in self.model.init(self.settings.L, self.dtype)
        )

    def restore_members(self, blocks: List, step: int) -> None:
        """Restore from per-member host field tuples (each field the
        true ``L^3`` domain, declaration order, from the member-indexed
        checkpoint stores).

        Host-side stack + one sharded device_put: ensemble restores are
        N small solo restores, not a selection-read fan-out — fine at
        ensemble scale (members are small by construction; huge-L runs
        use few members).
        """
        if len(blocks) != self.n_members:
            raise ValueError(
                f"restore_members got {len(blocks)} member states for "
                f"{self.n_members} members"
            )
        L = self.settings.L
        expected = (L, L, L)
        nf = self.model.n_fields
        per_field = [[] for _ in range(nf)]
        for i, member_fields in enumerate(blocks):
            member_fields = tuple(member_fields)
            if len(member_fields) != nf:
                raise ValueError(
                    f"member {i} checkpoint has {len(member_fields)} "
                    f"fields; model {self.model.name!r} declares {nf}"
                )
            for j, (name, f) in enumerate(
                zip(self.model.field_names, member_fields)
            ):
                f = jnp.asarray(f, self.dtype)
                if f.shape != expected:
                    raise ValueError(
                        f"member {i} checkpoint shape {name}={f.shape} "
                        f"does not match L={L}"
                    )
                per_field[j].append(f)
        stacked = [jnp.stack(fs) for fs in per_field]
        if self.mesh is not None and self.domain.padded:
            pads = [(0, 0)] + [
                (0, g - L) for g in self.domain.storage_shape
            ]
            stacked = [
                jnp.pad(f, pads, constant_values=bv)
                for f, bv in zip(stacked, self.model.boundaries)
            ]
        target = (
            self.field_sharding if self.mesh is not None else self.device
        )
        self.fields = tuple(
            jax.device_put(f, target) for f in stacked
        )
        self.step = int(step)
