"""The unified D4M streaming session: one entry point, three engines.

:class:`D4MStream` is the facade the paper's Fig. 1 workflow reads through:
construct it from a validated :class:`~repro.d4m.config.StreamConfig`, feed
triples with :meth:`~D4MStream.update` / :meth:`~D4MStream.ingest`, and
analyse through :meth:`~D4MStream.snapshot` and the bound
:attr:`~D4MStream.query` namespace.  The session picks the right engine
automatically:

* ``single`` — K=1 on one device: the ``lax.cond`` cascade
  (:func:`repro.core.hierarchical.update_triples`), which only pays for
  layer merges when a cut actually fires;
* ``packed`` — K>1 on one device: the branchless vmapped cascade
  (:func:`repro.core.multistream.packed_update`), K independent instances
  in one fused program;
* ``pallas`` — K>=1 on one device: the lane-skipping cascade kernel
  (:mod:`repro.kernels.hier_cascade`); one grid lane per instance, layer
  merges predicated on each lane's own cut checks, so the no-cascade step
  costs O(batch) instead of the branchless path's Σ layer caps (auto-picked
  on TPU backends; force with ``engine="pallas"`` or ``REPRO_D4M_ENGINE``);
* ``mesh`` — D>1: :class:`repro.core.multistream.MultiStreamEngine`
  (``shard_map``; K x D instances, zero update-path collectives).

This module also holds the *canonical* step builders the legacy
:mod:`repro.core.streaming` entry points now shim onto:
:func:`build_update_step`, :func:`scan_ingest`, and
:func:`scan_ingest_and_snapshot`.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import analytics, assoc, hierarchical, multistream
from repro.core.assoc import Assoc
from repro.core.hierarchical import HierAssoc
from repro.core.multistream import MultiStreamEngine
from repro.core.semiring import PLUS_TIMES, Semiring
from repro.core.telemetry import TelemetrySnapshot

from .config import CapacityPlan, ServeConfig, StreamConfig


# ---------------------------------------------------------------------------
# canonical step builders (the session's internals; legacy streaming.* shims
# delegate here)
# ---------------------------------------------------------------------------

def build_update_step(
    cuts: Sequence[int],
    sr: Semiring = PLUS_TIMES,
    donate: bool = True,
    instances: int | None = None,
):
    """A jitted ``(h, rows, cols, vals) -> h`` single-batch update.

    The hierarchy argument is donated so layer buffers are updated in place —
    on TPU this is what keeps layer 1 resident in fast memory; donation is
    just as load-bearing for the packed path, whose stacked buffers are K
    times larger.

    With ``instances=K`` the returned function updates a packed K-instance
    hierarchy from ``[K, B]`` triple batches (each instance cascades
    independently via the branchless masked cascade).
    """
    cuts = tuple(int(c) for c in cuts)

    if instances is None:

        def step(h: HierAssoc, rows, cols, vals) -> HierAssoc:
            return hierarchical.update_triples(h, rows, cols, vals, cuts, sr)

    else:
        k = int(instances)

        def step(h: HierAssoc, rows, cols, vals) -> HierAssoc:
            if rows.shape[0] != k:
                raise ValueError(
                    f"expected [{k}, B] instance-major triples, got {rows.shape}"
                )
            return multistream.packed_update(h, rows, cols, vals, cuts, sr)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def scan_ingest(
    h: HierAssoc,
    rows: jax.Array,  # [T, B] int32, or [T, K, B] when instances=K
    cols: jax.Array,
    vals: jax.Array,
    cuts: Sequence[int],
    sr: Semiring = PLUS_TIMES,
    instances: int | None = None,
    branchless: bool | None = None,
) -> Tuple[HierAssoc, jax.Array]:
    """``lax.scan`` a stream of triple batches into the hierarchy.

    Returns the final hierarchy and the per-step total-nnz trace (telemetry
    mirroring the paper's nnz-vs-updates plot, Fig. 3).  With ``instances=K``
    the stream is ``[T, K, B]``, ``h`` is a packed K-instance hierarchy, and
    the trace is the per-step *per-instance* nnz, shape ``[T, K]``.
    ``branchless`` forces the masked cascade (see
    :func:`repro.core.hierarchical.update`); ``None`` keeps each path's
    default (cond single-instance, auto for the pack).
    """
    cuts = tuple(int(c) for c in cuts)

    if instances is None:

        def body(carry: HierAssoc, batch):
            r, c, v = batch
            nxt = hierarchical.update_triples(
                carry, r, c, v, cuts, sr, branchless=bool(branchless)
            )
            return nxt, hierarchical.nnz_total(nxt)

    else:
        if rows.ndim != 3 or rows.shape[1] != int(instances):
            raise ValueError(
                f"expected [T, {int(instances)}, B] instance-major stream, "
                f"got {rows.shape}"
            )

        def body(carry: HierAssoc, batch):
            r, c, v = batch
            nxt = multistream.packed_update(
                carry, r, c, v, cuts, sr, branchless=branchless
            )
            return nxt, multistream.nnz_per_instance(nxt)

    return lax.scan(body, h, (rows, cols, vals))


@functools.partial(
    jax.jit, static_argnames=("cuts", "sr", "cap", "instances")
)
def scan_ingest_and_snapshot(
    h: HierAssoc,
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    cuts: Tuple[int, ...],
    cap: int,
    sr: Semiring = PLUS_TIMES,
    instances: int | None = None,
):
    """Stream ingest followed by a full snapshot (analysis handoff point).

    With ``instances=K`` the stream is ``[T, K, B]`` into a packed hierarchy
    and the returned snapshot is the *global* array — the semiring sum of all
    K per-instance snapshots (hash routing makes that a disjoint union).
    """
    h2, trace = scan_ingest(h, rows, cols, vals, cuts, sr, instances=instances)
    if instances is None:
        snap = hierarchical.snapshot(h2, cap=cap, sr=sr)
    else:
        per = multistream.snapshot_packed(h2, cap=cap, sr=sr)
        snap = multistream.merge_snapshots(per, cap=cap, sr=sr)
    return h2, snap, trace


# jitted snapshot programs (static cap/semiring).  Eagerly-dispatched
# snapshots re-interpret the whole merge pipeline per call — tens of
# seconds at real capacities, which the query plane's per-publish snapshot
# cannot afford; one compile per (cap, engine shape) amortizes to
# milliseconds.
@functools.partial(jax.jit, static_argnames=("cap", "sr"))
def _snapshot_single(h: HierAssoc, cap: int, sr: Semiring) -> Assoc:
    return hierarchical.snapshot(h, cap=cap, sr=sr)


@functools.partial(jax.jit, static_argnames=("cap", "sr", "merge"))
def _snapshot_packed(h: HierAssoc, cap: int, sr: Semiring, merge: bool):
    per = multistream.snapshot_packed(h, cap=cap, sr=sr)
    return multistream.merge_snapshots(per, cap=cap, sr=sr) if merge else per


# the pallas engine's state is in the kernel's tiled layout; these convert
# it (off the update path) for snapshots, checkpoints and restores
def _tile(h: HierAssoc, sr: Semiring) -> HierAssoc:
    from repro.kernels.hier_cascade import ops as cascade_ops

    return cascade_ops.tile_state(h, sr)


@functools.partial(jax.jit, static_argnames=("widths",))
def _untile(h: HierAssoc, widths) -> HierAssoc:
    from repro.kernels.hier_cascade import ops as cascade_ops

    return cascade_ops.untile_state(h, widths)


@functools.partial(jax.jit, static_argnames=("cap", "sr", "merge", "widths"))
def _snapshot_tiled(h: HierAssoc, cap: int, sr: Semiring, merge: bool, widths):
    return _snapshot_packed(_untile(h, widths), cap, sr, merge)


# ---------------------------------------------------------------------------
# the read side: immutable published views + the bound query namespace
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamView:
    """One immutable, owned read view of a streaming session.

    A view is the query plane's unit of snapshot isolation: the session
    (or the serve loop, at microbatch boundaries) *publishes* a view, and
    every query against it answers over exactly the records folded in at
    publication time — concurrent ingest never blocks on a reader and a
    reader never tears a half-applied microbatch.  ``snap`` holds fresh
    buffers produced by the snapshot computation (never aliases of the
    donated engine state), so a view stays valid indefinitely, across any
    number of later updates, restores or resets.

    * ``seq`` — publication sequence number (monotone per session; an
      unpublished library-mode view reports the latest published seq);
    * ``records`` — source records folded into this view when the publisher
      knows it (the serve loop's ``records_fed``); ``None`` in library mode,
      where the session does not meter triples through ``update()``;
    * ``nnz`` / ``overflowed`` — state counters at publication.

    Degree vectors are cached per capacity on first use — and pre-seeded by
    the serve loop's incremental :class:`~repro.serve.query.DegreeTracker`
    — so ``degrees``/``top_k`` never recompute a full reduction per call.
    """

    snap: Assoc
    sr: Semiring
    plan: CapacityPlan
    engine: str
    seq: int
    records: Optional[int] = None
    published_at: float = 0.0
    nnz: Optional[int] = None
    overflowed: Optional[bool] = None
    _degree_cache: Dict[int, Tuple[Assoc, Assoc]] = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )

    def _cap(self, cap: int | None) -> int:
        return int(cap) if cap is not None else self.plan.snapshot_cap

    def degrees(self, cap: int | None = None) -> Tuple[Assoc, Assoc]:
        """(out_degree, in_degree) keyed ``(vertex, 0)``, folded with the
        view semiring's add; cached per capacity (and pre-seeded by the
        serve loop's incremental tracker)."""
        cap = self._cap(cap)
        if cap not in self._degree_cache:
            self._degree_cache[cap] = analytics.degrees(
                self.snap, cap=cap, sr=self.sr
            )
        return self._degree_cache[cap]

    def top_k(self, k: int = 10, by: str = "out") -> Tuple[jax.Array, jax.Array]:
        """Heaviest-k vertices by out/in degree: ``(ids [k], counts [k])``.
        Reads the cached degree vectors — O(k) on a warm view."""
        out_deg, in_deg = self.degrees()
        return analytics.top_k_vertices(out_deg if by == "out" else in_deg, k)

    def triangles(
        self, cap_sq: int | None = None, max_fanout: int | None = None
    ) -> jax.Array:
        """Triangle count of the undirected support (tr(A^3)/6).

        A *count*, so it is always computed over the boolean support under
        plus.times, whatever semiring the session streams under (e.g. a
        max.plus session's sr.one = 0.0 would annihilate every product).
        """
        und = analytics.undirected_view(
            self.snap, cap=2 * self.plan.snapshot_cap, sr=PLUS_TIMES
        )
        return analytics.triangle_count(
            und,
            cap_sq=cap_sq if cap_sq is not None else 4 * self.plan.snapshot_cap,
            max_fanout=max_fanout if max_fanout is not None else self.plan.max_fanout,
        )

    def common_neighbors(self, u: int, v: int, cap: int | None = None) -> jax.Array:
        return analytics.common_neighbors(self.snap, u, v, cap=self._cap(cap))

    def jaccard(self, u: int, v: int, cap: int | None = None) -> jax.Array:
        return analytics.jaccard(self.snap, u, v, cap=self._cap(cap))

    def reachable_within(
        self, steps: int, cap: int | None = None, max_fanout: int | None = None
    ) -> Assoc:
        return analytics.reachable_within(
            self.snap,
            steps,
            cap=self._cap(cap),
            max_fanout=max_fanout if max_fanout is not None else self.plan.max_fanout,
        )

    def row(self, r: int, cap: int | None = None) -> Assoc:
        """Row slice ``A(r, :)`` — Fig. 1's nearest-neighbours query."""
        return assoc.extract_row(self.snap, r, cap=self._cap(cap), sr=self.sr)

    def get(self, r, c) -> jax.Array:
        """Point query ``A(r, c)``."""
        return assoc.get(self.snap, r, c, sr=self.sr)

    def stats(self) -> Dict[str, Any]:
        """Publication metadata as a JSON-ready dict (the ``stats`` wire op)."""
        return {
            "seq": int(self.seq),
            "records": None if self.records is None else int(self.records),
            "engine": self.engine,
            "nnz": None if self.nnz is None else int(self.nnz),
            "overflowed": None if self.overflowed is None else bool(self.overflowed),
            "published_at": float(self.published_at),
        }


class QueryNamespace:
    """Bound analytics over the session's *current read view*.

    Every method binds to a :class:`StreamView` and fills capacity
    arguments from the session's :class:`CapacityPlan`, so the paper's
    analyses are one-liners: ``sess.query.top_k(10)``,
    ``sess.query.triangles()``, ``sess.query.jaccard(u, v)``.

    Binding: while a serve loop is active the namespace answers over the
    *latest published view* — snapshot-isolated, never touching the donated
    device state the feed loop is mutating.  Outside a serve it answers
    over a lazily-built view of the live state (cached until the next
    update, as before).  Querying live state *during* a serve that
    publishes no views falls back to the old direct snapshot with a
    ``DeprecationWarning``: that read races the update path and will be
    removed — turn on ``ServeConfig.publish_every`` and use the view API.
    """

    def __init__(self, session: "D4MStream"):
        self._s = session

    def _resolve(self) -> StreamView:
        s = self._s
        if s._serving:
            v = s.latest_view()
            if v is not None:
                return v
            warnings.warn(
                "querying live mutable session state during an active serve "
                "is deprecated (the read races the donated update path): set "
                "ServeConfig.publish_every to publish snapshot-isolated "
                "views and bind through D4MStream.view()/latest_view()",
                DeprecationWarning,
                stacklevel=3,
            )
        return s._current_view()

    def _snap(self) -> Assoc:
        return self._resolve().snap

    def degrees(self, cap: int | None = None) -> Tuple[Assoc, Assoc]:
        """(out_degree, in_degree) keyed ``(vertex, 0)``, folded with the
        session semiring's add."""
        return self._resolve().degrees(cap)

    def top_k(self, k: int = 10, by: str = "out") -> Tuple[jax.Array, jax.Array]:
        """Heaviest-k vertices by out/in degree: ``(ids [k], counts [k])``."""
        return self._resolve().top_k(k, by)

    def triangles(
        self, cap_sq: int | None = None, max_fanout: int | None = None
    ) -> jax.Array:
        """Triangle count of the undirected support — see
        :meth:`StreamView.triangles`."""
        return self._resolve().triangles(cap_sq, max_fanout)

    def common_neighbors(self, u: int, v: int, cap: int | None = None) -> jax.Array:
        return self._resolve().common_neighbors(u, v, cap)

    def jaccard(self, u: int, v: int, cap: int | None = None) -> jax.Array:
        return self._resolve().jaccard(u, v, cap)

    def reachable_within(
        self, steps: int, cap: int | None = None, max_fanout: int | None = None
    ) -> Assoc:
        return self._resolve().reachable_within(steps, cap, max_fanout)

    def row(self, r: int, cap: int | None = None) -> Assoc:
        """Row slice ``A(r, :)`` — Fig. 1's nearest-neighbours query."""
        return self._resolve().row(r, cap)

    def get(self, r, c) -> jax.Array:
        """Point query ``A(r, c)``."""
        return self._resolve().get(r, c)


# ---------------------------------------------------------------------------
# the session facade
# ---------------------------------------------------------------------------

class D4MStream:
    """One streaming D4M session over the engine the config calls for.

    State lives inside the session (donated on every update, so the layer
    buffers are reused in place); :meth:`snapshot` / :attr:`query` are the
    read side.  See the module docstring for the engine-selection rules.
    """

    def __init__(
        self,
        config: StreamConfig,
        *,
        mesh: Mesh | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_keep: int = 3,
    ):
        config.validate()
        if mesh is not None:
            # an explicit mesh pins the device axis: fold it into the config
            # so plan()/telemetry report the true instance count
            n_mesh = 1
            for a in mesh.axis_names:
                n_mesh *= mesh.shape[a]
            config = dataclasses.replace(config, devices=n_mesh, engine="mesh")
        self.config = config
        self.plan: CapacityPlan = config.plan()
        self.cuts = config.resolved_cuts()
        self.sr = config.sr
        self.dtype = config.jnp_dtype
        self.batch_size = int(config.batch_size)
        self.k_per_device = int(config.instances_per_device)
        self._ckpt_dir = checkpoint_dir
        self._ckpt_keep = checkpoint_keep
        self._mgr = None
        self._snap_cache: Dict[Tuple[int, bool], Assoc] = {}
        self._query: Optional[QueryNamespace] = None
        # the query plane's read side: published immutable views + the
        # library-mode live view (invalidated on every mutation)
        self._view_seq = 0
        self._published_view: Optional[StreamView] = None
        self._live_view: Optional[StreamView] = None
        self._serving = False  # set by D4MServer while its feed loop owns state
        self._obs = None  # view-build histogram handle, set by D4MServer

        if mesh is not None:
            self.kind = "mesh"
            self.mesh = mesh
        else:
            self.kind = config.resolved_engine()
            self.mesh = None
            if self.kind == "mesh":
                d = config.resolved_devices()
                devs = jax.devices()
                if d > len(devs):
                    raise ValueError(
                        f"config asks for {d} devices but only {len(devs)} are "
                        f"available (force more with XLA_FLAGS="
                        f"--xla_force_host_platform_device_count=N)"
                    )
                self.mesh = Mesh(
                    np.asarray(devs[:d]).reshape(d), (config.axis_name,)
                )

        if self.kind == "mesh":
            self.engine = MultiStreamEngine(
                self.mesh,
                self.cuts,
                top_capacity=config.top_capacity,
                batch_size=self.batch_size,
                instances_per_device=self.k_per_device,
                sr=self.sr,
                dtype=self.dtype,
                branchless=config.branchless,
            )
            self.n_instances = self.engine.n_instances
            self._step = self.engine.update
        elif self.kind == "packed":
            self.engine = None
            self.n_instances = self.k_per_device
            k = self.n_instances
            cuts, sr, branchless = self.cuts, self.sr, config.branchless

            def _packed(h, rows, cols, vals):
                return multistream.packed_update(
                    h, rows, cols, vals, cuts, sr, branchless=branchless
                )

            self._step = jax.jit(_packed, donate_argnums=(0,))
            self._route = jax.jit(
                lambda r, c, v: multistream.route_to_instances(
                    r, c, v, k, self.batch_size, sr
                )
            )
        elif self.kind == "pallas":
            from repro.kernels.common import next_pow2
            from repro.kernels.hier_cascade import ops as cascade_ops

            self.engine = None
            self.n_instances = self.k_per_device
            k = self.n_instances
            sr = self.sr
            # compiled on a TPU, interpreted elsewhere;
            # a plan whose widest merge overflows VMEM fails here, not at
            # the first step
            cascade_ops.check_plan(self.plan.layer_caps, self.batch_size, self.dtype)
            # the state lives in the kernel's tiled layout (see `state`);
            # these are its pow2-padded [K, w] widths
            self._widths = tuple(next_pow2(c) for c in self.plan.layer_caps)
            self._step = cascade_ops.build_step(
                self.cuts, self.plan.layer_caps, sr, donate=True, tiled=True
            )
            self._route = jax.jit(
                lambda r, c, v: multistream.route_to_instances(
                    r, c, v, k, self.batch_size, sr
                )
            )
        else:  # single
            self.engine = None
            self.n_instances = 1
            cuts, sr = self.cuts, self.sr
            branchless = bool(config.branchless)

            def _single(h, rows, cols, vals):
                return hierarchical.update_triples(
                    h, rows, cols, vals, cuts, sr, branchless=branchless
                )

            self._step = jax.jit(_single, donate_argnums=(0,))

        self._state: Optional[HierAssoc] = None  # allocated lazily

    # -- lifecycle -----------------------------------------------------------
    @property
    def state(self) -> HierAssoc:
        """The live hierarchy pytree (allocated on first touch), in the
        engine's own layout: the ``pallas`` engine's layers are
        ``[K, w / 128, 128]`` (:func:`~repro.kernels.hier_cascade.ops.tile_state`);
        :meth:`flat_state` gives every engine's ``[K, w]`` form."""
        if self._state is None:
            self._state = self._init_state()
        return self._state

    @state.setter
    def state(self, value: HierAssoc) -> None:
        self._state = value

    def flat_state(self) -> HierAssoc:
        """The state with ``[.., w]`` layer lanes (a copy on ``pallas``)."""
        if self.kind != "pallas":
            return self.state
        return _untile(self.state, self._widths)

    def _init_state(self) -> HierAssoc:
        if self.kind == "mesh":
            return self.engine.init_state()
        if self.kind in ("packed", "pallas"):
            h = multistream.init_packed(
                self.n_instances,
                self.cuts,
                top_capacity=self.config.top_capacity,
                batch_size=self.batch_size,
                sr=self.sr,
                dtype=self.dtype,
                # the cascade kernel's bitonic networks stream over pow2-
                # padded persistent buffers (hierarchical.pad_layers_pow2)
                pad_pow2=(self.kind == "pallas"),
            )
            return _tile(h, self.sr) if self.kind == "pallas" else h
        return hierarchical.init(
            self.cuts,
            top_capacity=self.config.top_capacity,
            batch_size=self.batch_size,
            sr=self.sr,
            dtype=self.dtype,
        )

    @classmethod
    def from_dict(cls, config: Dict[str, Any], **kwargs) -> "D4MStream":
        """Build a session from :meth:`StreamConfig.to_dict` wire form.

        The fleet controller plans one config, ships it to each worker
        subprocess as JSON over the control channel, and the worker rebuilds
        an identical session here — so every host in the fleet is provably
        running the same validated plan.  ``kwargs`` pass through to the
        constructor (``checkpoint_dir=``, ...).
        """
        return cls(StreamConfig.from_dict(config), **kwargs)

    def reset(self) -> "D4MStream":
        """Fresh empty state (same compiled update functions)."""
        self.state = self._init_state()
        self._invalidate()
        return self

    @property
    def raw_update(self):
        """The jitted, state-donating ``(h, rows, cols, vals) -> h`` step —
        for benchmarks that need ``.lower()``/HLO inspection."""
        return self._step

    # -- write side ----------------------------------------------------------
    def update(self, rows, cols, vals) -> "D4MStream":
        """One pre-shaped batch: ``[B]`` (single), ``[K, B]`` (packed), or
        ``[K*D, B]`` instance-major (mesh; see :meth:`shard_stream`).

        State is donated — the previous ``self.state`` buffers are consumed.
        """
        self.state = self._step(self.state, rows, cols, vals)
        self._invalidate()
        return self

    def ingest(self, rows, cols, vals):
        """One *flat global* triple batch ``[B]``: hash-route to every
        instance, then update.  Returns the dropped-triple count (always 0
        for the single-instance engine; routing back pressure otherwise).
        """
        if self.kind == "single":
            self.update(rows, cols, vals)
            return jnp.zeros((), jnp.int32)
        if self.kind in ("packed", "pallas"):
            br, bc, bv, dropped = self._route(rows, cols, vals)
            self.update(br, bc, bv)
            return dropped
        self.state, dropped = self.engine.ingest(self.state, rows, cols, vals)
        self._invalidate()
        return dropped

    def ingest_stream(self, rows, cols, vals) -> jax.Array:
        """Scan a whole on-device stream: ``[T, B]`` (single) or
        ``[T, K, B]`` pre-routed (packed).  Returns the per-step nnz
        trace (``[T]`` or ``[T, K]``).

        Not offered on the mesh engine: its verified program is the
        per-batch ``shard_map`` update (zero collectives) — scan there with
        a loop over :meth:`update`.
        """
        if self.kind == "mesh":
            raise NotImplementedError(
                "ingest_stream is not available on the mesh engine; loop "
                "over update() so every step runs the verified shard_map "
                "program"
            )
        if self.kind == "pallas":
            from repro.kernels.hier_cascade import ops as cascade_ops

            if rows.ndim != 3 or rows.shape[1] != self.n_instances:
                raise ValueError(
                    f"expected [T, {self.n_instances}, B] instance-major "
                    f"stream, got {rows.shape}"
                )
            cuts, caps, sr = self.cuts, self.plan.layer_caps, self.sr

            def body(carry: HierAssoc, batch):
                r, c, v = batch
                nxt = cascade_ops.cascade_update_tiled(carry, r, c, v, cuts, caps, sr)
                return nxt, multistream.nnz_per_instance(nxt)

            self.state, trace = lax.scan(body, self.state, (rows, cols, vals))
            self._invalidate()
            return trace
        instances = None if self.kind == "single" else self.n_instances
        self.state, trace = scan_ingest(
            self.state, rows, cols, vals, self.cuts, self.sr,
            instances=instances, branchless=self.config.branchless,
        )
        self._invalidate()
        return trace

    def shard_stream(self, rows, cols, vals):
        """Place pre-split ``[n_instances, B]`` triples instance-major
        (mesh engine; identity elsewhere)."""
        if self.kind == "mesh":
            return self.engine.shard_stream(rows, cols, vals)
        return rows, cols, vals

    def route(self, rows, cols, vals):
        """Hash-split a flat global batch into per-instance sub-batches
        without updating (``(rows, cols, vals, dropped)``)."""
        if self.kind == "single":
            return rows, cols, vals, jnp.zeros((), jnp.int32)
        if self.kind in ("packed", "pallas"):
            return self._route(rows, cols, vals)
        return self.engine.route(rows, cols, vals)

    # -- read side -----------------------------------------------------------
    def snapshot(self, cap: int | None = None, per_instance: bool = False) -> Assoc:
        """Materialize ``A = sum_i A_i``.

        Global by default (for multi-instance engines: the semiring sum of
        every instance snapshot — a disjoint union under hash routing);
        ``per_instance=True`` returns the ``[n_instances]``-leading stack.
        ``cap`` defaults to the plan's ``snapshot_cap``.
        """
        cap = int(cap) if cap is not None else self.plan.snapshot_cap
        key = (cap, per_instance)
        if key in self._snap_cache:
            return self._snap_cache[key]
        if self.kind == "single":
            if per_instance:
                raise ValueError("single-instance session has no per-instance axis")
            snap = _snapshot_single(self.state, cap, self.sr)
        elif self.kind == "packed":
            snap = _snapshot_packed(
                self.state, cap, self.sr, merge=not per_instance
            )
        elif self.kind == "pallas":
            snap = _snapshot_tiled(
                self.state, cap, self.sr, merge=not per_instance,
                widths=self._widths,
            )
        else:
            snap = (
                self.engine.snapshot(self.state, cap)
                if per_instance
                else self.engine.snapshot_global(self.state, cap)
            )
        if not per_instance and bool(snap.overflow) and not self.overflowed():
            # the *state* fit but the snapshot cap did not: entries were
            # dropped while materializing — never let that pass silently
            import warnings

            warnings.warn(
                f"snapshot(cap={cap}) truncated the merged array "
                f"(overflow flag set); raise snapshot_cap in StreamConfig "
                f"or pass cap= explicitly",
                RuntimeWarning,
                stacklevel=2,
            )
        self._snap_cache[key] = snap
        return snap

    def _invalidate(self) -> None:
        """Every mutation path lands here: drop the cached snapshots and the
        library-mode live view.  Published views are deliberately NOT
        dropped — they are owned, immutable reads that stay answerable
        until the next publication replaces them."""
        self._snap_cache.clear()
        self._live_view = None

    def view(
        self,
        cap: int | None = None,
        *,
        records: int | None = None,
        degrees: Tuple[Assoc, Assoc] | None = None,
        publish: bool = True,
    ) -> StreamView:
        """Materialize an owned, immutable :class:`StreamView` of the
        current state.

        ``publish=True`` (default) assigns the next view sequence number
        and makes it the session's :meth:`latest_view` — what the serve
        loop does at microbatch boundaries, and what :attr:`query` binds
        to during a serve.  ``records`` stamps the source-record count the
        publisher has folded in (the staleness reference); ``degrees``
        pre-seeds the view's degree cache (the serve loop passes its
        incrementally-maintained vectors so ``top_k``/``degrees`` never
        re-reduce).

        The view's buffers are snapshot outputs — fresh arrays, never
        aliases of the donated engine state — so it remains valid across
        any later updates, restores, or resets (the same ownership rule
        checkpoints follow).
        """
        seq = self._view_seq + 1 if publish else self._view_seq
        _t0 = 0 if self._obs is None else time.perf_counter_ns()
        v = StreamView(
            snap=self.snapshot(cap),
            sr=self.sr,
            plan=self.plan,
            engine=self.kind,
            seq=seq,
            records=None if records is None else int(records),
            published_at=time.monotonic(),
            nnz=self.nnz(),
            overflowed=self.overflowed(),
        )
        if self._obs is not None:
            self._obs.record(time.perf_counter_ns() - _t0)
        if degrees is not None:
            v._degree_cache[v._cap(cap)] = degrees
        if publish:
            self._view_seq = seq
            self._published_view = v
        return v

    def latest_view(self) -> Optional[StreamView]:
        """The most recently *published* view (``None`` before the first
        publication).  Safe to read from any thread — publication swaps a
        single reference."""
        return self._published_view

    def _current_view(self) -> StreamView:
        """Library-mode read view: lazily built over the cached live
        snapshot, invalidated by the next mutation (NOT published)."""
        if self._live_view is None:
            self._live_view = self.view(publish=False)
        return self._live_view

    def nnz(self) -> int:
        """Total distinct-key upper bound across all instances."""
        if self.kind == "single":
            return int(hierarchical.nnz_total(self.state))
        return int(multistream.nnz_total(self.state))

    def overflowed(self) -> bool:
        """Sticky: any instance exceeded a static capacity somewhere."""
        if self.kind == "single":
            return bool(hierarchical.overflowed(self.state))
        return bool(multistream.overflowed_per_instance(self.state).any())

    def telemetry(self) -> TelemetrySnapshot:
        """Typed device-side counters for dashboards/benchmarks.

        Returns a :class:`repro.core.telemetry.TelemetrySnapshot`; it still
        reads like the old dict (``tel["nnz_total"]``) via the mapping shim.
        """
        snap = TelemetrySnapshot(
            engine=self.kind,
            n_instances=self.n_instances,
            instances_per_device=self.k_per_device,
            nnz_total=self.nnz(),
            overflowed=self.overflowed(),
            state_bytes=self.plan.total_bytes,
        )
        if self.kind == "single":
            snap.nnz_per_layer = [int(l.nnz) for l in self.state.layers]
            snap.cascades = np.asarray(self.state.cascades)
        else:
            snap.nnz_per_instance = np.asarray(
                multistream.nnz_per_instance(self.state)
            )
            snap.cascades_per_instance = np.asarray(self.state.cascades)
            snap.overflowed_per_instance = np.asarray(
                multistream.overflowed_per_instance(self.state)
            )
        return snap

    @property
    def query(self) -> QueryNamespace:
        if self._query is None:
            self._query = QueryNamespace(self)
        return self._query

    # -- serving (wires repro.serve) -----------------------------------------
    def serve(
        self,
        source,
        serve_config: ServeConfig | None = None,
        timeout: float | None = None,
        **overrides,
    ):
        """Serve a record source into this session until it drains.

        ``source`` is any :class:`repro.serve.Source` (TCP loopback socket,
        tailed file, synthetic R-MAT traffic, pre-materialized arrays); the
        ingress loop batches, hash-routes, and feeds it through this
        session's engine with bounded-queue backpressure, then drains and
        returns a :class:`repro.serve.ServeReport`.

        Config resolution: explicit ``serve_config`` wins, then the
        ``serve=`` field on this session's :class:`StreamConfig`, then
        defaults; keyword ``overrides`` patch individual fields either way
        (``sess.serve(src, max_latency_ms=5)``).  For manual control —
        live telemetry, mid-stream stop — construct a
        :class:`repro.serve.D4MServer` directly.
        """
        from repro.serve import D4MServer

        cfg = serve_config or self.config.serve or ServeConfig()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        return D4MServer(self, source, cfg).run(timeout=timeout)

    # -- fault tolerance (wires checkpoint.manager) --------------------------
    def _manager(self):
        if self._ckpt_dir is None:
            raise ValueError(
                "session has no checkpoint_dir; pass checkpoint_dir= to D4MStream"
            )
        if self._mgr is None:
            from repro.checkpoint.manager import CheckpointManager

            self._mgr = CheckpointManager(self._ckpt_dir, keep=self._ckpt_keep)
        return self._mgr

    def checkpoint(self, step: int, extra: Dict[str, Any] | None = None) -> None:
        """Async atomic save of the full hierarchy state (+ stream cursor
        metadata in ``extra``); overlaps serialization with compute."""
        self._manager().save_async(step, self.flat_state(), extra=extra)

    def wait_checkpoint(self) -> None:
        self._manager().wait()

    def restore(
        self, step: int | None = None, fallback: bool | None = None
    ) -> Dict[str, Any]:
        """Restore state from the latest (or given) checkpoint; returns the
        saved ``extra`` metadata (e.g. the stream cursor).  ``fallback``
        (default: on when no step is pinned) walks back past torn/corrupt
        generations to the newest one that verifies — see
        :meth:`repro.checkpoint.manager.CheckpointManager.restore`."""
        mgr = self._manager()
        mgr.wait()
        like = jax.tree.map(jnp.zeros_like, self.flat_state())
        state, extra = mgr.restore(like, step=step, shardings=None,
                                   fallback=fallback)
        # The manager returns host (numpy) leaves.  They must come back as
        # device arrays that OWN their buffers (an explicit copy, never
        # jnp.asarray / a device_put of the manager's array): on the CPU
        # backend those can be zero-copy views of numpy-owned memory, and
        # the session's donating update steps would then hand XLA a buffer
        # it doesn't own — heap corruption on the first post-restore update
        # (caught by the serve replay test).  On the mesh the owned copy
        # stays on the HOST (np.array, not jnp.array) and device_put places
        # it sharded in one step: the full unsharded leaf must never be
        # staged on the default device, or states that only fit sharded
        # across D devices would OOM device 0 on restore.
        if self.kind == "mesh":
            sh = NamedSharding(self.mesh, P(self.engine.axes))
            state = jax.tree.map(
                lambda x: jax.device_put(np.array(x, copy=True), sh), state
            )
        else:
            state = jax.tree.map(lambda x: jnp.array(x, copy=True), state)
        if self.kind == "pallas":
            state = _tile(state, self.sr)
        self.state = state
        self._invalidate()
        return extra

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (
            f"D4MStream(engine={self.kind}, instances={self.n_instances}, "
            f"layers={self.plan.n_layers}, sr={self.sr.name})"
        )
