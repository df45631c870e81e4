"""Instance-packed multi-stream engine (paper Section V's scaling axis).

NOTE: this is the *engine layer*.  The public entry point is the unified
session API — :class:`repro.d4m.D4MStream` with
``StreamConfig(instances_per_device=K, devices=D)`` — which constructs and
drives this engine; call it directly only when building new engine-level
machinery.

The paper's 1.9 B updates/s does not come from one fast array — it comes from
34,000 *independent* hierarchical D4M instances, each ingesting its own slice
of the stream with zero update-path communication (see also arXiv:1902.00846).
:class:`~repro.core.distributed.ParallelHierStream` maps exactly one
:class:`~repro.core.hierarchical.HierAssoc` per device, so on a laptop or a
single CI host the instance-scaling axis is capped at the device count.

This module removes that cap: **K independent instances per device**, packed
by stacking every layer buffer along a leading instance axis and ``jax.vmap``-
ing the hierarchical cascade.  ``lax.cond`` does not vectorize into
independent per-lane branches, so the packed path uses the *branchless*
cascade (``hierarchical.update(..., branchless=True)``): every cut check
becomes a ``jnp.where`` select, letting each instance cascade independently
inside one fused program.  Composed with the device mesh via ``shard_map``
this gives K x D total instances and — exactly like the paper — an update
path containing **zero collectives** (verified structurally in
``benchmarks/bench_scaling.py``).

A hash-based :func:`route_to_instances` splitter (the sort-scatter idiom of
``distributed.bucket_by_owner_sorted``) fans one global triple stream out to
all K x D instances.  Routing is keyed on ``(row, col)``, so a given key is
always owned by the same instance: each instance's snapshot is the exact
restriction of the global array to its key subset, and the global array is
the collision-free semiring sum of all instance snapshots
(:func:`merge_snapshots`).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import assoc, hierarchical
from .telemetry import TelemetrySnapshot
from .assoc import Assoc, PAD
from .hierarchical import HierAssoc
from .semiring import PLUS_TIMES, Semiring


# ---------------------------------------------------------------------------
# packed state: a HierAssoc whose leaves carry a leading [K] instance axis
# ---------------------------------------------------------------------------

def init_packed(
    n_instances: int,
    cuts: Sequence[int],
    top_capacity: int,
    batch_size: int,
    sr: Semiring = PLUS_TIMES,
    dtype=jnp.float32,
    pad_pow2: bool = False,
) -> HierAssoc:
    """``n_instances`` independent empty hierarchies, stacked per leaf.

    The result is an ordinary :class:`HierAssoc` pytree whose every leaf has a
    leading ``[n_instances]`` axis — instance ``k`` is the slice ``leaf[k]``.

    ``pad_pow2=True`` grows every layer buffer to the next power of two
    (:func:`repro.core.hierarchical.pad_layers_pow2`) — the persistent flat
    layout the ``hier_cascade`` Pallas kernel streams over.  Semantics are
    unchanged; only buffer tails grow.
    """
    h = hierarchical.init(cuts, top_capacity, batch_size, sr, dtype)
    if pad_pow2:
        h = hierarchical.pad_layers_pow2(h, sr)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_instances,) + x.shape), h
    )


def flat_layer_state(h: HierAssoc):
    """A packed hierarchy's buffers in the flat layout the ``hier_cascade``
    kernel consumes: per-layer ``(rows, cols, vals)`` triples (each
    ``[K, cap_i]``) plus the stacked ``[K, L]`` scalar planes (nnz counters,
    cascade counters, overflow flags).  Pure re-arrangement — no copies of
    the key/value lanes."""
    bufs = tuple((l.rows, l.cols, l.vals) for l in h.layers)
    nnz = jnp.stack([l.nnz for l in h.layers], axis=1)
    overflow = jnp.stack([l.overflow for l in h.layers], axis=1)
    return bufs, nnz, h.cascades, overflow


def from_flat_layer_state(bufs, nnz, cascades, overflow) -> HierAssoc:
    """Inverse of :func:`flat_layer_state` — reassemble the packed pytree
    from the kernel's output planes."""
    layers = tuple(
        Assoc(rows=r, cols=c, vals=v, nnz=nnz[:, i], overflow=overflow[:, i])
        for i, (r, c, v) in enumerate(bufs)
    )
    return HierAssoc(layers=layers, cascades=cascades)


def packed_update(
    h: HierAssoc,
    rows: jax.Array,  # [K, B] int32
    cols: jax.Array,  # [K, B]
    vals: jax.Array,  # [K, B]
    cuts: Sequence[int],
    sr: Semiring = PLUS_TIMES,
    branchless: bool | None = None,
) -> HierAssoc:
    """One streaming update on every packed instance at once.

    Semantically identical to ``K`` separate ``hierarchical.update_triples``
    calls (see ``tests/core/test_multistream.py`` for the bit-exact
    equivalence check); structurally a single vmapped branchless cascade, so
    all K instances run as one fused device program.

    By default (``branchless=None``) ``K = 1`` skips the vmap and keeps the
    ``lax.cond`` cascade: with a single instance there is nothing to mask,
    and the cond path only pays for layer merges when a cut actually fires
    (the seed's per-device cost profile, which ``ParallelHierStream`` users
    rely on).  ``branchless=True`` forces the masked cascade even at K = 1 —
    the instance-scaling benchmark uses it so every sweep point runs the
    same per-instance program.
    """
    cuts = tuple(int(c) for c in cuts)
    if rows.shape[0] == 1 and branchless is not True:
        h1 = jax.tree.map(lambda x: x[0], h)
        h1 = hierarchical.update_triples(
            h1, rows[0], cols[0], vals[0], cuts, sr
        )
        return jax.tree.map(lambda x: x[None], h1)

    def one(hi: HierAssoc, r, c, v) -> HierAssoc:
        return hierarchical.update_triples(
            hi, r, c, v, cuts, sr, branchless=True
        )

    return jax.vmap(one)(h, rows, cols, vals)


# ---------------------------------------------------------------------------
# packed telemetry / snapshots
# ---------------------------------------------------------------------------

def nnz_per_instance(h: HierAssoc) -> jax.Array:
    """Per-instance upper bound on distinct keys; ``[K]`` int32."""
    return jax.vmap(hierarchical.nnz_total)(h)


def nnz_total(h: HierAssoc) -> jax.Array:
    """Sum of per-instance nnz across the whole pack."""
    return jnp.sum(nnz_per_instance(h))


def overflowed_per_instance(h: HierAssoc) -> jax.Array:
    """Sticky per-instance overflow flags; ``[K]`` bool."""
    return jax.vmap(hierarchical.overflowed)(h)


def cascades_per_instance(h: HierAssoc) -> jax.Array:
    """Per-instance cascade counters; ``[K, n_layers]`` int32."""
    return h.cascades


def snapshot_packed(h: HierAssoc, cap: int, sr: Semiring = PLUS_TIMES) -> Assoc:
    """Per-instance ``A = sum_i A_i``; an Assoc with leading ``[K]`` axis.

    A ``lax.map`` over the instances, not a ``vmap``: XLA's TPU compiler
    takes minutes over batched merges at real widths and seconds over one
    unbatched merge body, and snapshots are off the update path.
    """
    return lax.map(lambda hi: hierarchical.snapshot(hi, cap=cap, sr=sr), h)


def merge_snapshots(snap: Assoc, cap: int, sr: Semiring = PLUS_TIMES) -> Assoc:
    """Fold a packed ``[K]``-leading snapshot into one global Assoc.

    A left fold of ``assoc.add`` over the instance axis (one merge body,
    compiled once; see :func:`snapshot_packed`).  With hash routing the
    instances hold disjoint key subsets, so this is a pure disjoint union;
    the semiring add keeps it correct for arbitrary (overlapping) packs too.
    """
    acc = assoc.empty(cap, sr, snap.vals.dtype)
    acc, _ = lax.scan(lambda a, s: (assoc.add(a, s, cap=cap, sr=sr), None), acc, snap)
    return acc


# ---------------------------------------------------------------------------
# hash routing: one global triple stream -> K x D instance sub-streams
# ---------------------------------------------------------------------------

_H1 = np.uint32(0x9E3779B1)  # golden-ratio multiplicative constants
_H2 = np.uint32(0x85EBCA77)
_M1 = np.uint32(0x7FEB352D)  # murmur-style finalizer multipliers; the host
_M2 = np.uint32(0x846CA68B)  # router (repro.serve.router) imports all four
#                              so its mirror can never silently diverge

#: Width of the routing hash.  The two routing tiers consume disjoint ends
#: of the same :func:`key_hash32` output: the **instance** tier takes the
#: hash modulo K (the low-entropy end, here and in the host mirror
#: ``repro.serve.router.instance_of_numpy``) while the **host** tier of a
#: multi-process fleet (``repro.fleet.routing.route_host``) takes the top
#: bits — ``(hash * n_hosts) >> 32``, the exact top ``log2(n_hosts)`` bits
#: when ``n_hosts`` is a power of two.  One finalizer, two provably
#: independent prefixes: a retune of the constants above reaches every tier
#: mechanically.
KEY_HASH_BITS = 32


def key_hash32(rows: jax.Array, cols: jax.Array) -> jax.Array:
    """The finalized 32-bit key hash every routing tier consumes — a
    murmur-style integer finalizer over ``(row, col)`` so R-MAT power-law
    hot rows still spread evenly.  Returns uint32."""
    x = rows.astype(jnp.uint32) * _H1 + cols.astype(jnp.uint32) * _H2
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


def instance_of(rows: jax.Array, cols: jax.Array, n_instances: int) -> jax.Array:
    """Which of ``n_instances`` owns key ``(row, col)``: the low end of
    :func:`key_hash32` (modulo) — see :data:`KEY_HASH_BITS` for how this
    composes with the fleet's host tier."""
    x = key_hash32(rows, cols)
    return (x % np.uint32(n_instances)).astype(jnp.int32)


def scatter_to_slots(
    owner: jax.Array,  # [B] int32 in [0, n_slots); entries with live=False ignored
    live: jax.Array,  # [B] bool
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    n_slots: int,
    slot_cap: int,
    sr: Semiring = PLUS_TIMES,
):
    """O(B log B) sort-scatter of a triple batch into ``[n_slots, slot_cap]``.

    The generic core of ``distributed.bucket_by_owner_sorted`` and
    :func:`route_to_instances`: stable-sort by owner, rank within each run,
    scatter to fixed-size slots.  Triples beyond ``slot_cap`` in any one slot
    are counted in ``dropped`` (back pressure is surfaced, never silent).
    """
    owner = jnp.where(live, owner, n_slots)  # park dead entries in a virtual slot
    order = jnp.argsort(owner, stable=True)
    owner_s = owner[order]
    idx = jnp.arange(rows.shape[0], dtype=jnp.int32)
    start = jnp.searchsorted(owner_s, owner_s, side="left").astype(jnp.int32)
    rank = idx - start
    live_s = live[order]
    dropped = jnp.sum((rank >= slot_cap) & live_s)
    slot = jnp.where(
        (rank < slot_cap) & live_s, owner_s * slot_cap + rank, n_slots * slot_cap
    )
    out_r = jnp.full((n_slots * slot_cap,), PAD, jnp.int32).at[slot].set(
        rows[order], mode="drop"
    )
    out_c = jnp.full((n_slots * slot_cap,), PAD, jnp.int32).at[slot].set(
        cols[order], mode="drop"
    )
    out_v = (
        jnp.full((n_slots * slot_cap,), sr.zero, vals.dtype)
        .at[slot]
        .set(vals[order], mode="drop")
    )
    shape = (n_slots, slot_cap)
    return out_r.reshape(shape), out_c.reshape(shape), out_v.reshape(shape), dropped


def route_to_instances(
    rows: jax.Array,  # [B] int32 (PAD = dead slot)
    cols: jax.Array,
    vals: jax.Array,
    n_instances: int,
    slot_cap: int,
    sr: Semiring = PLUS_TIMES,
):
    """Split one global triple batch into per-instance sub-batches.

    Returns ``(rows, cols, vals, dropped)`` with shapes
    ``[n_instances, slot_cap]``; routing is the deterministic key hash
    :func:`instance_of`, so replaying the same stream always produces the
    same sub-streams (what the packed-vs-sequential equivalence test relies
    on).
    """
    owner = instance_of(rows, cols, n_instances)
    live = rows != PAD
    return scatter_to_slots(
        owner, live, rows, cols, vals, n_instances, slot_cap, sr
    )


# ---------------------------------------------------------------------------
# mesh composition: K instances per device x D devices
# ---------------------------------------------------------------------------

class MultiStreamEngine:
    """K independent hierarchies per device, composed over the device mesh.

    State is one packed :class:`HierAssoc` with a leading ``[K * D]`` instance
    axis, sharded across the mesh on that axis; each device updates its local
    ``[K]`` block with the vmapped branchless cascade inside ``shard_map``.
    Like the paper's deployment the hot update path has **zero collectives**;
    global telemetry (`global_nnz`) uses a ``psum`` outside the hot loop.
    """

    def __init__(
        self,
        mesh: Mesh,
        cuts: Sequence[int],
        top_capacity: int,
        batch_size: int,
        instances_per_device: int = 1,
        sr: Semiring = PLUS_TIMES,
        axis_names: Tuple[str, ...] | None = None,
        dtype=jnp.float32,
        branchless: bool | None = None,
    ):
        if instances_per_device < 1:
            raise ValueError(f"instances_per_device must be >= 1, got {instances_per_device}")
        self.branchless = branchless
        self.mesh = mesh
        self.cuts = tuple(int(c) for c in cuts)
        self.sr = sr
        self.batch_size = int(batch_size)
        self.instances_per_device = int(instances_per_device)
        self.axes = tuple(axis_names or mesh.axis_names)
        self.n_devices = 1
        for a in self.axes:
            self.n_devices *= mesh.shape[a]
        self.n_instances = self.n_devices * self.instances_per_device
        self.top_capacity = int(top_capacity)
        self.dtype = dtype
        spec = P(self.axes)
        self._state_spec = spec

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
        def _update(h, rows, cols, vals):
            # local block: leaves [K, ...], triples [K, B] — no collectives.
            return packed_update(
                h, rows, cols, vals, self.cuts, self.sr,
                branchless=self.branchless,
            )

        self.update = jax.jit(_update, donate_argnums=(0,))

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(spec,),
            out_specs=P(),
            check_vma=False,
        )
        def _global_nnz(h):
            local = nnz_total(h)
            for ax in self.axes:
                local = lax.psum(local, ax)
            return local

        self.global_nnz = jax.jit(_global_nnz)
        self._route = jax.jit(
            lambda r, c, v: route_to_instances(
                r, c, v, self.n_instances, self.batch_size, self.sr
            )
        )
        # per-cap jitted snapshot builders: cached so repeated telemetry
        # calls hit the jit cache instead of retracing every time
        self._snapshot_fn = functools.lru_cache(maxsize=8)(
            lambda cap: jax.jit(
                jax.shard_map(
                    lambda hh: snapshot_packed(hh, cap=cap, sr=self.sr),
                    mesh=mesh,
                    in_specs=(spec,),
                    out_specs=spec,
                    check_vma=False,
                )
            )
        )
        self._merge_fn = functools.lru_cache(maxsize=8)(
            lambda cap: jax.jit(
                lambda s: merge_snapshots(s, cap=cap, sr=self.sr)
            )
        )

    # -- state & stream placement ------------------------------------------
    def init_state(self) -> HierAssoc:
        """Packed empty hierarchies, instance-sharded across the mesh."""
        h = init_packed(
            self.n_instances,
            self.cuts,
            self.top_capacity,
            self.batch_size,
            self.sr,
            self.dtype,
        )
        sh = NamedSharding(self.mesh, self._state_spec)
        return jax.tree.map(lambda x: jax.device_put(x, sh), h)

    def shard_stream(self, rows, cols, vals):
        """Place pre-split ``[n_instances, B]`` triples instance-major."""
        sh = NamedSharding(self.mesh, P(self.axes))
        return tuple(jax.device_put(x, sh) for x in (rows, cols, vals))

    # -- ingestion ----------------------------------------------------------
    def route(self, rows, cols, vals):
        """Hash-split a flat global triple batch to all instances.

        Returns ``(rows, cols, vals, dropped)``; the triples are placed with
        instance-major sharding, ready for :meth:`update`.
        """
        br, bc, bv, dropped = self._route(rows, cols, vals)
        return (*self.shard_stream(br, bc, bv), dropped)

    def ingest(self, h: HierAssoc, rows, cols, vals):
        """Route one flat global batch and update every instance.

        This is the single-feeder convenience path; steady-state producers
        should route on their own thread/host and call :meth:`update`.
        """
        br, bc, bv, dropped = self.route(rows, cols, vals)
        return self.update(h, br, bc, bv), dropped

    # -- analysis -----------------------------------------------------------
    def snapshot(self, h: HierAssoc, cap: int) -> Assoc:
        """Per-instance snapshots, ``[n_instances]``-leading Assoc."""
        return self._snapshot_fn(int(cap))(h)

    def snapshot_global(self, h: HierAssoc, cap: int) -> Assoc:
        """One global Assoc: semiring sum of every instance snapshot."""
        return self._merge_fn(int(cap))(self.snapshot(h, cap))

    def telemetry(self, h: HierAssoc) -> TelemetrySnapshot:
        """Packed counters for dashboards/benchmarks (host-side values);
        a typed :class:`~repro.core.telemetry.TelemetrySnapshot` that still
        reads like the old dict via its mapping shim."""
        return TelemetrySnapshot(
            engine="mesh",
            nnz_per_instance=np.asarray(nnz_per_instance(h)),
            cascades_per_instance=np.asarray(cascades_per_instance(h)),
            overflowed_per_instance=np.asarray(overflowed_per_instance(h)),
            nnz_total=int(nnz_total(h)),
            n_instances=self.n_instances,
            instances_per_device=self.instances_per_device,
        )
