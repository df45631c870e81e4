"""Distributed hierarchical associative arrays.

NOTE: for the paper-faithful independent-instance design, the public entry
point is now :class:`repro.d4m.D4MStream` (``StreamConfig(devices=D)``);
:class:`ParallelHierStream` below is a deprecation shim over it.

Two designs, mirroring the paper and going one step beyond it:

* :class:`ParallelHierStream` — the paper's scaling design (Section V):
  every device owns an *independent* ``HierAssoc`` instance and ingests its
  own slice of the stream.  The update path has **zero collectives**, which is
  exactly why the paper scales linearly to 34,000 instances; global telemetry
  (total nnz, aggregate rate) uses a ``psum`` outside the hot loop.

* :func:`route_updates` / :class:`ShardedAssoc` — beyond-paper: one *global*
  array sharded by row-key range.  Each device buckets its locally observed
  triples by owner and exchanges them with a single ``all_to_all``, then
  ingests only its own range.  This is the production "one table, many
  writers" design the paper delegates to Accumulo, rebuilt on the TPU
  interconnect.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import assoc, hierarchical, multistream
from .assoc import Assoc, PAD
from .hierarchical import HierAssoc
from .multistream import MultiStreamEngine
from .semiring import PLUS_TIMES, Semiring


# ---------------------------------------------------------------------------
# paper-faithful: independent instances, zero update-path collectives
# ---------------------------------------------------------------------------

class ParallelHierStream:
    """DEPRECATED: one independent hierarchical array per device.

    Thin shim over the unified session API — construction builds a
    :class:`repro.d4m.D4MStream` on the given mesh and forwards to its
    engine.  New code should use the session directly::

        sess = repro.d4m.D4MStream(
            repro.d4m.StreamConfig(cuts=..., top_capacity=..., batch_size=...,
                                   devices=D, instances_per_device=K))

    (The functional ``init_state()/update(h, ...)`` surface here maps onto
    the session's internally-held state + ``update()``.)
    """

    def __init__(
        self,
        mesh: Mesh,
        cuts: Sequence[int],
        top_capacity: int,
        batch_size: int,
        sr: Semiring = PLUS_TIMES,
        axis_names: Tuple[str, ...] | None = None,
        instances_per_device: int = 1,
    ):
        import warnings

        warnings.warn(
            "ParallelHierStream is deprecated; use repro.d4m.D4MStream "
            "(the unified session API)",
            DeprecationWarning,
            stacklevel=2,
        )
        if axis_names is not None and tuple(axis_names) != tuple(mesh.axis_names):
            # sub-axis meshes predate the session API; keep the old direct path
            self.engine = MultiStreamEngine(
                mesh,
                cuts,
                top_capacity,
                batch_size,
                instances_per_device=instances_per_device,
                sr=sr,
                axis_names=axis_names,
            )
        else:
            from repro.d4m import D4MStream, StreamConfig

            self.session = D4MStream(
                StreamConfig(
                    cuts=tuple(int(c) for c in cuts),
                    top_capacity=int(top_capacity),
                    batch_size=int(batch_size),
                    semiring=sr,
                    instances_per_device=int(instances_per_device),
                    engine="mesh",
                ),
                mesh=mesh,
            )
            self.engine = self.session.engine
        self.mesh = mesh
        self.cuts = self.engine.cuts
        self.sr = sr
        self.batch_size = batch_size
        self.axes = self.engine.axes
        self.n_instances = self.engine.n_instances
        # jitted engine entry points, donated state, zero update collectives
        self.update = self.engine.update
        self.global_nnz = self.engine.global_nnz

    def init_state(self) -> HierAssoc:
        """Per-device hierarchies, stacked on a leading instance axis."""
        return self.engine.init_state()

    def shard_stream(self, rows, cols, vals):
        """Place a [n_instances, B] triple batch with instance-major sharding."""
        return self.engine.shard_stream(rows, cols, vals)

    def ingest(self, h: HierAssoc, rows, cols, vals):
        """Hash-route a flat global triple batch to every instance and update."""
        return self.engine.ingest(h, rows, cols, vals)


# ---------------------------------------------------------------------------
# beyond paper: key-range-sharded global array with all_to_all routing
# ---------------------------------------------------------------------------

def owner_of(rows: jax.Array, n_shards: int, key_space: int) -> jax.Array:
    """Contiguous row-range ownership: shard i owns rows in
    ``[i*key_space/n, (i+1)*key_space/n)``."""
    per = max(1, key_space // n_shards)
    return jnp.clip(rows // per, 0, n_shards - 1).astype(jnp.int32)


def bucket_by_owner(
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    n_shards: int,
    key_space: int,
    slot_cap: int,
    sr: Semiring = PLUS_TIMES,
):
    """Group a local triple batch into ``n_shards`` fixed-size slots.

    Returns ``[n_shards, slot_cap]`` arrays ready for ``all_to_all``.  Slots
    overflowing ``slot_cap`` set the returned ``dropped`` counter (back
    pressure is surfaced, not silently lost).
    """
    owner = owner_of(rows, n_shards, key_space)
    live = rows != PAD
    owner = jnp.where(live, owner, n_shards)  # park pads in a virtual shard
    # stable position of each triple within its owner bucket
    one = live.astype(jnp.int32)
    # rank within bucket = number of earlier entries with same owner
    same = owner[None, :] == owner[:, None]
    earlier = jnp.tril(jnp.ones_like(same), k=-1)
    rank = jnp.sum(same & earlier.astype(bool), axis=1).astype(jnp.int32)
    dropped = jnp.sum((rank >= slot_cap) & live)
    slot = jnp.where((rank < slot_cap) & live, owner * slot_cap + rank, n_shards * slot_cap)
    out_r = jnp.full((n_shards * slot_cap,), PAD, jnp.int32).at[slot].set(rows, mode="drop")
    out_c = jnp.full((n_shards * slot_cap,), PAD, jnp.int32).at[slot].set(cols, mode="drop")
    out_v = (
        jnp.full((n_shards * slot_cap,), sr.zero, vals.dtype).at[slot].set(vals, mode="drop")
    )
    shape = (n_shards, slot_cap)
    return out_r.reshape(shape), out_c.reshape(shape), out_v.reshape(shape), dropped


def bucket_by_owner_sorted(
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    n_shards: int,
    key_space: int,
    slot_cap: int,
    sr: Semiring = PLUS_TIMES,
):
    """O(B log B) bucketing via sort (production path; the quadratic-rank
    variant above is kept as the readable reference for tests).

    The sort-scatter core is shared with the hash router
    (:func:`multistream.scatter_to_slots`); only the ownership function
    differs — contiguous key ranges here, key hashing there.
    """
    owner = owner_of(rows, n_shards, key_space)
    live = rows != PAD
    return multistream.scatter_to_slots(
        owner, live, rows, cols, vals, n_shards, slot_cap, sr
    )


class ShardedAssoc:
    """A single global hierarchical array, sharded by row-key range.

    ``update``: every device buckets its batch by owner, one ``all_to_all``
    exchanges the buckets, and each device ingests triples for its own range
    into its local ``HierAssoc``.  Query for a key routes to its owner.
    """

    def __init__(
        self,
        mesh: Mesh,
        axis: str,
        cuts: Sequence[int],
        top_capacity: int,
        batch_size: int,
        key_space: int,
        slot_cap: int | None = None,
        sr: Semiring = PLUS_TIMES,
    ):
        self.mesh = mesh
        self.axis = axis
        self.n_shards = mesh.shape[axis]
        self.key_space = key_space
        self.cuts = tuple(int(c) for c in cuts)
        self.sr = sr
        # worst case a device's whole batch goes to one owner
        self.slot_cap = slot_cap or batch_size
        ingest_cap = self.n_shards * self.slot_cap
        self._init = lambda: hierarchical.init(
            self.cuts, top_capacity, ingest_cap, sr
        )
        other_axes = tuple(a for a in mesh.axis_names if a != axis)
        spec_state = P(axis)
        spec_batch = P(axis)

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(spec_state, spec_batch, spec_batch, spec_batch),
            out_specs=(spec_state, P()),
            check_vma=False,
        )
        def _update(h, rows, cols, vals):
            h = jax.tree.map(lambda x: x[0], h)
            r, c, v = rows[0], cols[0], vals[0]
            br, bc, bv, dropped = bucket_by_owner_sorted(
                r, c, v, self.n_shards, key_space, self.slot_cap, sr
            )
            # exchange buckets: shard axis of the leading dim
            br = lax.all_to_all(br, axis, 0, 0, tiled=False)
            bc = lax.all_to_all(bc, axis, 0, 0, tiled=False)
            bv = lax.all_to_all(bv, axis, 0, 0, tiled=False)
            flat = lambda x: x.reshape((-1,))
            h = hierarchical.update_triples(
                h, flat(br), flat(bc), flat(bv), self.cuts, sr
            )
            dropped = lax.psum(dropped, axis)
            for ax in other_axes:
                dropped = lax.pmax(dropped, ax)
            return jax.tree.map(lambda x: x[None], h), dropped

        self.update = jax.jit(_update, donate_argnums=(0,))

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(spec_state, P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        def _get(h, r, c):
            h = jax.tree.map(lambda x: x[0], h)
            snap_cap = h.layers[-1].capacity
            mine = owner_of(r, self.n_shards, key_space) == lax.axis_index(axis)
            snap = hierarchical.snapshot(h, cap=snap_cap, sr=sr)
            val = assoc.get(snap, r, c, sr)
            val = jnp.where(mine, val, jnp.asarray(sr.zero, val.dtype))
            out = lax.psum(val, axis)
            for ax in other_axes:
                out = lax.pmax(out, ax)
            return out

        self.get = jax.jit(_get)

    def init_state(self) -> HierAssoc:
        n = self.n_shards
        h = self._init()
        h = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), h)
        sh = NamedSharding(self.mesh, P(self.axis))
        return jax.tree.map(lambda x: jax.device_put(x, sh), h)
