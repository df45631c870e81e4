"""Pallas kernel: lane-skipping hierarchical cascade for the packed engine.

One ``pallas_call`` executes a full streaming update step for K stacked
``HierAssoc`` instances.  The grid is the instance axis — each grid step
owns one instance's layer buffers — and, unlike the branchless vmapped
cascade (``hierarchical.update(..., branchless=True)``), a lane only pays
for the layer merges its own cut checks actually fire:

* the layer-1 insert (merge the canonicalized batch into the smallest layer)
  runs unconditionally — the O(batch + layer 1) fast path the paper's insert
  rates depend on;
* every layer-i -> i+1 merge sits under ``@pl.when(nnz_i > cut_i)``: lanes
  whose cut did not fire never touch the larger layers;
* the layer and batch buffers stay in HBM (``pl.ANY``) and are copied into
  VMEM only by the merges that read them; the layer buffers are
  ``input_output_aliases``-ed, so untouched layers are not copied at all.

Layout.  Every buffer is a lane-major ``[K, rows, 128]`` array: one
instance's flat sorted-COO lane of width ``w`` is the ``(w // 128, 128)``
tile matrix at ``[k]``, in row-major order (:func:`tile`; the ``pallas``
engine keeps its state in this layout, so nothing is relaid out around the
call).  A merge runs over VMEM scratch
refs of ``2 * w_dst`` elements and walks them in ``(8, 128)`` chunks (one
vreg per 32-bit array) inside ``fori_loop``s, so code size does not grow
with the layer width and only the scratch does.  Per-lane counters (nnz,
cascade counts, overflow flags) live in SMEM.

Algorithm (the same on every merge):

1. ``dst`` is copied to the first half of the scratch, ``src`` to the tail
   of the second half, and the second half is reversed in place, so the
   scratch holds the bitonic sequence ``dst ++ reversed(src ++ pads)``.
   Reversal is index XOR ``(n - 1)``: whole chunks swap ends and each
   ``(8, 128)`` tile is reversed by partner exchanges at every bit.
2. A bitonic merge over ``(row, col, src-tag)``: distances of a chunk or
   more compare whole chunk pairs; the shorter distances run inside a tile
   with ``pltpu.roll`` partners, all of them in one pass over the chunks.
3. One ascending pass folds duplicate keys (both inputs hold unique keys,
   so a run has at most two entries: ``sr.add(dst, src)``), marks the
   survivors, and turns the dead-slot count into each survivor's shift with
   a log-step shifted-add scan per tile plus a running carry.
4. Compaction in ``log2 n`` shift passes (survivor shifts are monotone, so
   moving them bit by bit never collides), then the true capacity mask.

Everything is a constant-stride move or a select: no gathers, no scatters,
no data-dependent indexing.  Results are bit-identical to ``assoc.add``
(``tests/kernels/test_hier_cascade.py`` runs the kernel in interpret mode
against both other engines).  Compiled, it runs on a TPU v5e chip as the
``pallas`` engine of ``D4MStream`` (``chip_smoke.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.assoc import PAD
from repro.core.semiring import PLUS_TIMES, Semiring

from .. import common

LANES = 128
CHUNK = 8  # rows per loop step: one (8, 128) vreg per 32-bit array
TILE = CHUNK * LANES  # smallest buffer width the kernel works on
# Scoped VMEM the kernel may claim.  TPU v5e and v6e have 128 MiB of VMEM
# per core; the rest is left to the compiler's own scratch.
VMEM_BUDGET_BYTES = 100 * 2**20
_VMEM_SLACK_BYTES = 4 * 2**20
_N_WORK = 5  # scratch arrays: rows, cols, src tag, vals, survivor shift


def kernel_width(w: int) -> int:
    """Width at which the kernel holds a buffer of ``w`` entries."""
    return max(TILE, common.next_pow2(w))


def tile(x: jax.Array, fill) -> jax.Array:
    """``[K, w]`` lanes -> the kernel's ``[K, w' / 128, 128]`` layout,
    ``w' = kernel_width(w)``, the tail filled with ``fill``."""
    k, w = x.shape
    kw = kernel_width(w)
    if kw != w:
        x = jnp.concatenate([x, jnp.full((k, kw - w), fill, x.dtype)], axis=1)
    return x.reshape(k, kw // LANES, LANES)


def untile(x: jax.Array, w: int) -> jax.Array:
    """Inverse of :func:`tile` for lanes of width ``w``."""
    return x.reshape(x.shape[0], -1)[:, :w]


def vmem_bytes(widths) -> int:
    """VMEM the kernel claims for layer (and batch) widths ``widths``: the
    32-bit work arrays of the widest merge, which spans twice its
    destination."""
    w = max(kernel_width(int(x)) for x in widths)
    return _N_WORK * 2 * w * 4 + _VMEM_SLACK_BYTES


def check_fits(widths, dtype) -> int:
    """Raise ``ValueError`` unless the kernel can hold buffers of ``widths``
    entries with ``dtype`` values; returns the VMEM bytes it will claim."""
    if jnp.dtype(dtype).itemsize != 4:
        raise ValueError(f"the hier_cascade kernel needs 32-bit values, got {jnp.dtype(dtype)}")
    need = vmem_bytes(widths)
    if need > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"the hier_cascade kernel needs {need / 2**20:.1f} MiB of VMEM "
            f"for layer widths {tuple(int(x) for x in widths)} (its widest "
            f"merge spans 2 x {max(kernel_width(int(x)) for x in widths):,} "
            f"entries), over the {VMEM_BUDGET_BYTES / 2**20:.0f} MiB limit; "
            f"lower top_capacity or use engine='packed'"
        )
    return need


# ---------------------------------------------------------------------------
# tile primitives: (CHUNK, 128) values in flat row-major order
# ---------------------------------------------------------------------------

def _iotas():
    row = lax.broadcasted_iota(jnp.int32, (CHUNK, LANES), 0)
    lane = lax.broadcasted_iota(jnp.int32, (CHUNK, LANES), 1)
    return row, lane, row * LANES + lane


def _roll(x, shift: int, axis: int):
    shift %= x.shape[axis]
    return x if shift == 0 else pltpu.roll(x, shift, axis)


def _partner(x, d: int):
    """``y[i] = x[i ^ d]`` inside one tile (``d`` a power of two < TILE)."""
    row, lane, _ = _iotas()
    if d < LANES:
        up, down, bit = _roll(x, d, 1), _roll(x, LANES - d, 1), lane & d
    else:
        dr = d // LANES
        up, down, bit = _roll(x, dr, 0), _roll(x, CHUNK - dr, 0), row & dr
    return jnp.where(bit != 0, up, down)


def _reverse_tile(x):
    """Flat reversal of one tile: index XOR (TILE - 1), one bit at a time."""
    d = 1
    while d < TILE:
        x = _partner(x, d)
        d *= 2
    return x


def _from_next(x, nxt, d: int):
    """``y[i] = flat[i + d]`` for the tile ``x`` followed by the tile
    ``nxt`` (``0 < d < TILE``)."""
    row, lane, _ = _iotas()
    if d < LANES:
        a = _roll(x, LANES - d, 1)
        b = _roll(a, CHUNK - 1, 0)
        nb = _roll(_roll(nxt, LANES - d, 1), CHUNK - 1, 0)
        return jnp.where(lane < LANES - d, a, jnp.where(row < CHUNK - 1, b, nb))
    dr = d // LANES
    return jnp.where(
        row < CHUNK - dr, _roll(x, CHUNK - dr, 0), _roll(nxt, CHUNK - dr, 0)
    )


def _from_prev(x, prev, d: int):
    """``y[i] = flat[i - d]`` for the tile ``x`` preceded by ``prev``."""
    row, lane, _ = _iotas()
    if d < LANES:
        a = _roll(x, d, 1)
        b = _roll(a, 1, 0)
        pb = _roll(_roll(prev, d, 1), 1, 0)
        return jnp.where(lane >= d, a, jnp.where(row >= 1, b, pb))
    dr = d // LANES
    return jnp.where(row >= dr, _roll(x, dr, 0), _roll(prev, dr, 0))


def _inclusive_scan(x):
    """Prefix sum over one tile's flat order: log-step shifted adds."""
    zero = jnp.zeros_like(x)
    d = 1
    while d < TILE:
        x = x + _from_prev(x, zero, d)
        d *= 2
    return x


def _lex_less(a, b):
    return common.lex_less(a[0], a[1], a[2], b[0], b[1], b[2])


# ---------------------------------------------------------------------------
# chunked passes over VMEM scratch refs
# ---------------------------------------------------------------------------

def _rows(j):
    return pl.ds(pl.multiple_of(j * CHUNK, CHUNK), CHUNK)


def _for(n: int, body, init=0):
    return lax.fori_loop(0, n, body, init)


def _fill(refs_vals, lo_chunk: int, n_chunks: int):
    """Store a constant in chunks ``[lo, lo + n)`` of each ``(ref, value)``."""
    if n_chunks <= 0:
        return

    def body(j, c):
        for ref, v in refs_vals:
            ref[_rows(lo_chunk + j), :] = jnp.full((CHUNK, LANES), v, ref.dtype)
        return c

    _for(n_chunks, body)


def _reverse_region(refs, lo_chunk: int, n_chunks: int):
    """Reverse the flat order of chunks ``[lo, lo + n)`` in place."""

    def body(j, c):
        a, b = lo_chunk + j, lo_chunk + n_chunks - 1 - j
        for ref in refs:
            xa, xb = ref[_rows(a), :], ref[_rows(b), :]
            ref[_rows(a), :] = _reverse_tile(xb)
            ref[_rows(b), :] = _reverse_tile(xa)
        return c

    _for(n_chunks // 2, body)
    if n_chunks == 1:
        for ref in refs:
            ref[_rows(lo_chunk), :] = _reverse_tile(ref[_rows(lo_chunk), :])


def _bitonic_merge(work, n_chunks: int):
    """Ascending bitonic merge of ``n_chunks`` chunks of ``work`` (rows,
    cols, tag, vals), which must hold a bitonic sequence."""
    half = n_chunks // 2
    while half >= 1:  # chunk-pair passes: distance half * TILE

        def pair(p, c, half=half):
            lo = (p // half) * 2 * half + p % half
            hi = lo + half
            a = [ref[_rows(lo), :] for ref in work]
            b = [ref[_rows(hi), :] for ref in work]
            swap = _lex_less(b, a)
            for ref, x, y in zip(work, a, b):
                ref[_rows(lo), :] = jnp.where(swap, y, x)
                ref[_rows(hi), :] = jnp.where(swap, x, y)
            return c

        _for(n_chunks // 2, pair)
        half //= 2

    def in_tile(j, c):  # every distance below a chunk, in registers
        _, _, flat = _iotas()
        xs = [ref[_rows(j), :] for ref in work]
        d = TILE // 2
        while d >= 1:
            ps = [_partner(x, d) for x in xs]
            lo = (flat & d) == 0
            take = (lo & _lex_less(ps, xs)) | (~lo & _lex_less(xs, ps))
            xs = [jnp.where(take, p, x) for p, x in zip(ps, xs)]
            d //= 2
        for ref, x in zip(work, xs):
            ref[_rows(j), :] = x
        return c

    _for(n_chunks, in_tile)


def _fold_and_mark(work, shift_ref, n_chunks: int, sr: Semiring):
    """Fold duplicate pairs, clear dead slots, and write each survivor's
    left shift (dead slots before it) into ``shift_ref`` (-1 when dead).
    Returns the survivor count."""
    rows_ref, cols_ref, _, vals_ref = work
    zero = jnp.asarray(sr.zero, vals_ref.dtype)
    last = n_chunks * TILE - 1

    def body(j, carry):
        pr_t, pc_t, pv_t, dead_before = carry
        _, _, flat = _iotas()
        g = j * TILE + flat
        r, c, v = rows_ref[_rows(j), :], cols_ref[_rows(j), :], vals_ref[_rows(j), :]
        jn = jnp.minimum(j + 1, n_chunks - 1)
        nr, nc = rows_ref[_rows(jn), :], cols_ref[_rows(jn), :]
        pr, pc, pv = (_from_prev(x, p, 1) for x, p in ((r, pr_t), (c, pc_t), (v, pv_t)))
        same_prev = (r == pr) & (c == pc) & (g >= 1)
        v_fold = jnp.where(same_prev, sr.add(pv, v), v)
        is_end = (r != _from_next(r, nr, 1)) | (c != _from_next(c, nc, 1)) | (g == last)
        keep = is_end & (r != PAD)
        dead = jnp.where(keep, 0, 1)
        before = _inclusive_scan(dead) - dead + dead_before
        rows_ref[_rows(j), :] = jnp.where(keep, r, PAD)
        cols_ref[_rows(j), :] = jnp.where(keep, c, PAD)
        vals_ref[_rows(j), :] = jnp.where(keep, v_fold, zero)
        shift_ref[_rows(j), :] = jnp.where(keep, before, -1)
        return r, c, v, dead_before + jnp.sum(dead)

    init_t = jnp.full((CHUNK, LANES), PAD, jnp.int32)
    init = (init_t, init_t, jnp.full((CHUNK, LANES), zero, vals_ref.dtype), jnp.int32(0))
    n_dead = _for(n_chunks, body, init)[3]
    return n_chunks * TILE - n_dead


def _compact(work, shift_ref, n_chunks: int, sr: Semiring):
    """Move every survivor left by its shift, one bit per pass.  Reads run
    ahead of writes (each pass is ascending and pulls from higher chunks),
    so every pass can work in place."""
    rows_ref, cols_ref, _, vals_ref = work
    lanes = (rows_ref, cols_ref, vals_ref, shift_ref)
    fills = (PAD, PAD, sr.zero, -1)
    d = 1
    while d < n_chunks * TILE:

        def body(j, c, d=d):
            xs = [ref[_rows(j), :] for ref in lanes]
            if d < TILE:
                jn = jnp.minimum(j + 1, n_chunks - 1)
                real = j + 1 < n_chunks
                ns = [ref[_rows(jn), :] for ref in lanes]
                ns = [jnp.where(real, n, jnp.asarray(f, n.dtype)) for n, f in zip(ns, fills)]
                ys = [_from_next(x, n, d) for x, n in zip(xs, ns)]
            else:
                jn = j + d // TILE
                real = jn < n_chunks
                ys = [ref[_rows(jnp.minimum(jn, n_chunks - 1)), :] for ref in lanes]
                ys = [jnp.where(real, y, jnp.asarray(f, y.dtype)) for y, f in zip(ys, fills)]
            s, ns = xs[3], ys[3]
            arriving = (ns >= 0) & ((ns & d) != 0)
            staying = (s >= 0) & ((s & d) == 0)
            for ref, x, y, f in zip(lanes, xs, ys, fills):
                fill = jnp.asarray(f, x.dtype)
                ref[_rows(j), :] = jnp.where(arriving, y, jnp.where(staying, x, fill))
            return c

        _for(n_chunks, body)
        d *= 2


def _merge(dst_hbm, src_hbm, work, shift_ref, *, cap_dst: int, sr: Semiring):
    """Merge one lane's canonical ``src`` into its canonical ``dst`` layer
    (``assoc.add(dst, src, cap=cap_dst)``).  ``dst_hbm``/``src_hbm`` are
    ``(rows, cols, vals)`` refs of shape ``(width // 128, 128)``.  The
    result overwrites ``dst_hbm``; returns ``(nnz, overflow)`` scalars."""
    rows_ref, cols_ref, tag_ref, vals_ref = work
    nd = dst_hbm[0].shape[0] // CHUNK
    ns = src_hbm[0].shape[0] // CHUNK
    zero = jnp.asarray(sr.zero, vals_ref.dtype)
    # dst ++ reversed(src ++ pads): src lands at the very end, then the
    # whole second half is reversed in place
    span = lambda lo, n: pl.ds(lo * CHUNK, n * CHUNK)
    for ref, hbm in zip((rows_ref, cols_ref, vals_ref), dst_hbm):
        pltpu.sync_copy(hbm, ref.at[span(0, nd)])
    for ref, hbm in zip((rows_ref, cols_ref, vals_ref), src_hbm):
        pltpu.sync_copy(hbm, ref.at[span(2 * nd - ns, ns)])
    _fill([(rows_ref, PAD), (cols_ref, PAD), (vals_ref, zero)], nd, nd - ns)
    _fill([(tag_ref, 0)], 0, nd)  # dst entries sort before equal src keys
    _fill([(tag_ref, 1)], nd, nd)
    # pads ++ reversed(src) == reversed(src ++ pads): only src needs turning
    _reverse_region((rows_ref, cols_ref, vals_ref), 2 * nd - ns, ns)
    _bitonic_merge(work, 2 * nd)
    n_surv = _fold_and_mark(work, shift_ref, 2 * nd, sr)
    _compact(work, shift_ref, 2 * nd, sr)
    # the true (unpadded) capacity, exactly like assoc._compact
    first = cap_dst // TILE
    if first < nd:

        def cap_body(j, c):
            _, _, flat = _iotas()
            out = (j + first) * TILE + flat >= cap_dst
            for ref, f in ((rows_ref, PAD), (cols_ref, PAD), (vals_ref, zero)):
                x = ref[_rows(j + first), :]
                ref[_rows(j + first), :] = jnp.where(out, jnp.asarray(f, x.dtype), x)
            return c

        _for(nd - first, cap_body)
    for ref, hbm in zip((rows_ref, cols_ref, vals_ref), dst_hbm):
        pltpu.sync_copy(ref.at[span(0, nd)], hbm)
    return jnp.minimum(n_surv, cap_dst), (n_surv > cap_dst).astype(jnp.int32)


def _clear(layer_hbm, work, sr: Semiring):
    """Empty one lane's layer (``assoc.empty``): PAD keys, zero values."""
    rows_ref, _, _, vals_ref = work
    n = layer_hbm[0].shape[0] // CHUNK
    _fill([(rows_ref, PAD), (vals_ref, jnp.asarray(sr.zero, vals_ref.dtype))], 0, n)
    span = pl.ds(0, n * CHUNK)
    pltpu.sync_copy(rows_ref.at[span], layer_hbm[0])
    pltpu.sync_copy(rows_ref.at[span], layer_hbm[1])
    pltpu.sync_copy(vals_ref.at[span], layer_hbm[2])


def _cascade_kernel(*refs, cuts, caps, sr: Semiring):
    """One grid step = one instance lane ``k``.  Ref order:
    in:  nnz, cascades, overflow (SMEM ``[K, L]`` int32), batch (rows,
         cols, vals), L x layer (rows, cols, vals) — HBM ``[K, w/128, 128]``
    out: nnz', cascades', overflow' (SMEM), L x layer (aliased)
    scratch: rows, cols, tag, vals, shift work arrays (VMEM)
    """
    n_layers = len(caps)
    nnz_in, casc_in, ov_in = refs[:3]
    batch = refs[3:6]
    n_in = 6 + 3 * n_layers
    nnz_o, casc_o, ov_o = refs[n_in : n_in + 3]
    layers = [refs[n_in + 3 + 3 * i : n_in + 6 + 3 * i] for i in range(n_layers)]
    work, shift_ref = refs[-5:-1], refs[-1]
    k = pl.program_id(0)
    lane = lambda bufs: tuple(b.at[k] for b in bufs)

    for i in range(n_layers):
        nnz_o[k, i] = nnz_in[k, i]
        casc_o[k, i] = casc_in[k, i]
        ov_o[k, i] = ov_in[k, i]

    # -- layer-1 insert: always runs ----------------------------------------
    n1, of1 = _merge(lane(layers[0]), lane(batch), work, shift_ref, cap_dst=caps[0], sr=sr)
    nnz_o[k, 0] = n1
    ov_o[k, 0] = ov_o[k, 0] | of1

    # -- cascade: layer i -> i+1 only where this lane's cut fired -----------
    for i, cut in enumerate(cuts):

        @pl.when(nnz_o[k, i] > cut)
        def _(i=i):
            mn, mof = _merge(
                lane(layers[i + 1]), lane(layers[i]), work, shift_ref,
                cap_dst=caps[i + 1], sr=sr,
            )
            nnz_o[k, i + 1] = mn
            ov_o[k, i + 1] = ov_o[k, i + 1] | ov_o[k, i] | mof
            # the source layer empties (assoc.empty: overflow resets)
            _clear(lane(layers[i]), work, sr)
            nnz_o[k, i] = 0
            ov_o[k, i] = 0
            casc_o[k, i + 1] = casc_o[k, i + 1] + 1


def hier_cascade_pallas(
    batch_bufs,
    nnz,
    cascades,
    overflow,
    layer_bufs,
    cuts,
    caps,
    sr: Semiring = PLUS_TIMES,
    interpret: bool | None = None,
):
    """Run one packed cascade step over all K instance lanes.

    ``batch_bufs`` = canonical batch ``(rows, cols, vals)`` each ``[K, QB]``
    (power-of-two padded); ``layer_bufs`` = per-layer ``(rows, cols, vals)``
    each ``[K, Q_i / 128, 128]``, the kernel's own layout
    (:func:`tile`, ``Q_i = kernel_width`` of the pow2-padded capacity);
    ``nnz``/``cascades`` ``[K, L]`` int32, ``overflow`` ``[K, L]`` bool.
    ``caps`` are the true telescoped capacities.  ``interpret=None``
    interprets off the TPU only (:func:`repro.kernels.common.resolve_interpret`).
    Returns ``(nnz', cascades', overflow', layer_bufs')``, the layers in the
    same layout and aliased to the inputs.
    """
    cuts = tuple(int(c) for c in cuts)
    caps = tuple(int(c) for c in caps)
    n_layers = len(caps)
    if len(cuts) != n_layers - 1:
        raise ValueError(f"{len(cuts)} cuts needs {len(cuts) + 1} layers, got {n_layers}")
    k = batch_bufs[0].shape[0]
    qb = batch_bufs[0].shape[1]
    for bufs in layer_bufs:
        if bufs[0].ndim != 3 or bufs[0].shape[2] != LANES:
            raise ValueError(f"layer buffers must be [K, w/{LANES}, {LANES}] (tile), got {bufs[0].shape}")
    widths = [bufs[0].shape[1] * LANES for bufs in layer_bufs]
    for q, cap in zip(widths, caps):
        if q & (q - 1) or q < cap:
            raise ValueError(
                f"layer buffers must be pow2-padded >= their true cap "
                f"(pad_layers_pow2), got width {q} for cap {cap}"
            )
    if qb & (qb - 1) or qb > widths[0]:
        raise ValueError(f"batch width {qb} must be pow2 and <= layer-1 width {widths[0]}")
    for qa, qb_ in zip(widths, widths[1:]):
        if qa > qb_:
            raise ValueError(f"layer widths must be non-decreasing, got {widths}")
    dtype = batch_bufs[2].dtype
    need = check_fits(widths, dtype)

    zero = jnp.asarray(sr.zero, dtype)
    fills = (PAD, PAD, zero)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    planes = [nnz.astype(jnp.int32), cascades.astype(jnp.int32), overflow.astype(jnp.int32)]
    operands = planes + [tile(b, f) for b, f in zip(batch_bufs, fills)]
    out_shape = [jax.ShapeDtypeStruct((k, n_layers), jnp.int32)] * 3
    aliases = {}
    for i, bufs in enumerate(layer_bufs):
        for j, buf in enumerate(bufs):
            aliases[len(operands)] = 3 + 3 * i + j
            operands.append(buf)
            out_shape.append(jax.ShapeDtypeStruct(buf.shape, buf.dtype))
    w2 = 2 * max(widths) // LANES
    kernel = functools.partial(_cascade_kernel, cuts=cuts, caps=caps, sr=sr)
    outs = pl.pallas_call(
        kernel,
        grid=(k,),
        out_shape=out_shape,
        in_specs=[smem] * 3 + [hbm] * (len(operands) - 3),
        out_specs=[smem] * 3 + [hbm] * (len(out_shape) - 3),
        scratch_shapes=[
            pltpu.VMEM((w2, LANES), jnp.int32),  # rows
            pltpu.VMEM((w2, LANES), jnp.int32),  # cols
            pltpu.VMEM((w2, LANES), jnp.int32),  # src tag
            pltpu.VMEM((w2, LANES), dtype),  # vals
            pltpu.VMEM((w2, LANES), jnp.int32),  # survivor shift
        ],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=need
        ),
        interpret=common.resolve_interpret(interpret),
        name="hier_cascade",
    )(*operands)
    nnz_o, casc_o, ov_o = outs[:3]
    layers_o = [tuple(outs[3 + 3 * i : 6 + 3 * i]) for i in range(n_layers)]
    return nnz_o, casc_o, ov_o.astype(jnp.bool_), layers_o
