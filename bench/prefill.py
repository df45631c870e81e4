"""A session's state at a deployment's fill, built from the seed.

A deployment that has streamed for a while holds live entries in every layer
of every instance.  The measured window starts from such a state: the
configuration's ``prefill`` names, for each layer, how many records each
instance's share of that layer was built from (``records_per_instance``).
:func:`fill` draws those records' keys on the device (the benchmark's own
Graph500 R-MAT, relabelled by the stream's seeded vertex permutation), routes
them to instances by the program's key hash (a copy of
``repro.core.multistream.key_hash32``), and on the host sorts them, folds
equal keys and writes each instance's sorted unique entries into the
layer's buffers, which replace the session's.  The layers' keys overlap as
they would after a real history: each layer holds records of its own period.

A key's value is its record count times a weight in (0, 1] drawn from the
key itself, so the host folds keys with one sort of packed 64-bit keys (a
sort that carries values costs ten times as much).  XLA's TPU compiler takes
minutes over a device sort of tens of millions of entries, so the device
only draws the records.

The builder imports nothing of the program.  It works on the state as the
session keeps it: every layer leaf is an instance-major buffer (``[w]`` for
one instance, ``[K, w]`` or the kernel's ``[K, w / 128, 128]``, a reshape of
``[K, w]``), dead slots keyed ``PAD`` with zero values, ``nnz`` a count per
instance.  The reference starts from the same host arrays.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import stream

PAD = np.iinfo(np.int32).max
# copy of repro.core.multistream's key hash constants (the instance router)
_H1, _H2 = np.uint32(0x9E3779B1), np.uint32(0x85EBCA77)
_M1, _M2 = np.uint32(0x7FEB352D), np.uint32(0x846CA68B)
_SEED_MOD = 1 << 32
_W1, _W2 = np.uint32(0x27D4EB2F), np.uint32(0x165667B1)  # key -> weight mixing


def instance_of(rows: np.ndarray, cols: np.ndarray, k: int) -> np.ndarray:
    """The instance that owns each key: the program's key hash modulo ``k``."""
    if k == 1:
        return np.zeros(rows.shape, np.int64)
    with np.errstate(over="ignore"):
        x = rows.astype(np.uint32) * _H1 + cols.astype(np.uint32) * _H2
        x ^= x >> np.uint32(16)
        x *= _M1
        x ^= x >> np.uint32(15)
        x *= _M2
        x ^= x >> np.uint32(16)
    return (x % np.uint32(k)).astype(np.int64)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _rmat(seed, perm, n: int, scale: int, a: float, b: float, c: float):
    """``n`` Graph500 R-MAT edges, relabelled by ``perm``, as int32 rows and cols."""
    key = jax.random.key(seed)

    def level(i, rc):
        r, col = rc
        u = jax.random.uniform(jax.random.fold_in(key, i), (n,))
        r = (r << 1) | (u >= a + b).astype(jnp.int32)
        col = (col << 1) | (((u >= a) & (u < a + b)) | (u >= a + b + c)).astype(jnp.int32)
        return r, col

    zero = jnp.zeros((n,), jnp.int32)
    r, col = jax.lax.fori_loop(0, scale, level, (zero, zero))
    return perm[r], perm[col]


def weight_of(rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """A weight in (0, 1] drawn from each key (float32)."""
    with np.errstate(over="ignore"):
        x = rows.astype(np.uint32) * _W1 + cols.astype(np.uint32) * _W2
        x ^= x >> np.uint32(15)
        x *= _W1
        x ^= x >> np.uint32(13)
    return ((x >> np.uint32(8)).astype(np.float32) + 1) / np.float32(1 << 24)


@dataclasses.dataclass
class Layer:
    """One prefilled layer on the host: every instance's live entries,
    instance-major and sorted by key within an instance."""

    lane: np.ndarray  # int64 instance of each entry
    rows: np.ndarray  # int32
    cols: np.ndarray  # int32
    vals: np.ndarray  # float32
    counts: np.ndarray  # [k] live entries per instance


def build_layer(seed: int, layer: int, n: int, k: int, rmat: Dict[str, Any], perm) -> Layer:
    """``n`` records drawn for ``layer``, routed to ``k`` instances and folded."""
    scale = int(rmat["scale"])
    dseed = jnp.asarray((int(seed) * 1000003 + layer) % _SEED_MOD, jnp.uint32)
    r, c = jax.device_get(_rmat(dseed, perm, int(n), scale, float(rmat["a"]),
                                float(rmat["b"]), float(rmat["c"])))
    lane = instance_of(r, c, k)
    mask = (1 << scale) - 1
    keys = np.sort((lane << (2 * scale)) | (r.astype(np.int64) << scale) | c.astype(np.int64))
    new = np.empty(keys.size, bool)
    new[0] = True
    np.not_equal(keys[1:], keys[:-1], out=new[1:])
    start = np.flatnonzero(new)
    count = np.diff(np.append(start, keys.size)).astype(np.float32)
    uk = keys[start]
    lane = uk >> (2 * scale)
    rows = ((uk >> scale) & mask).astype(np.int32)
    cols = (uk & mask).astype(np.int32)
    vals = count * weight_of(rows, cols)
    return Layer(lane, rows, cols, vals, np.bincount(lane, minlength=k))


def fill(sess, spec: Dict[str, Any], rmat: Dict[str, Any], seed: int,
         limits: Sequence[int]) -> Tuple[List[Tuple], List[np.ndarray]]:
    """Replace the layers of ``sess``'s state that ``spec`` names with built
    ones.  Returns every built instance-layer's live ``(rows, cols, vals)``
    for the reference and each built layer's per-instance counts, and
    refuses a layer that would stand over its ``limits`` entry (over its cut
    it would cascade at once)."""
    per = [int(x) for x in spec["records_per_instance"]]
    state = sess.state
    if len(per) != len(state.layers):
        raise ValueError(f"prefill names {len(per)} layers, the state has {len(state.layers)}")
    k = int(sess.n_instances)
    perm = jnp.asarray(stream.vertex_permutation(seed, int(rmat["scale"])))
    layers, parts, counts = list(state.layers), [], []
    for i, n in enumerate(per):
        if n == 0:
            continue
        got = build_layer(seed, i, n * k, k, rmat, perm)
        if int(got.counts.max()) > int(limits[i]):
            raise RuntimeError(f"prefill: layer {i + 1} holds {int(got.counts.max())} keys "
                               f"in an instance, over its limit {limits[i]}")
        old = layers[i]
        dtype = np.dtype(old.vals.dtype)
        vals = got.vals.astype(dtype)  # what the state holds, in its own precision
        first = np.cumsum(got.counts) - got.counts
        for x in (old.rows, old.cols, old.vals):
            x.delete()  # one copy of the layer on the device at a time
        width = old.rows.size // k
        bufs = []
        for src, fill_value, like in ((got.rows, PAD, old.rows), (got.cols, PAD, old.cols),
                                      (vals, 0, old.vals)):
            buf = np.full((k, width), fill_value, np.dtype(like.dtype))
            for j in range(k):  # an instance's entries are a prefix of its lane
                buf[j, :got.counts[j]] = src[first[j]:first[j] + got.counts[j]]
            bufs.append(jnp.array(buf.reshape(like.shape), copy=True))
        nnz = jnp.array(got.counts.astype(np.dtype(old.nnz.dtype)).reshape(old.nnz.shape), copy=True)
        layers[i] = dataclasses.replace(old, rows=bufs[0], cols=bufs[1], vals=bufs[2], nnz=nnz)
        for j in range(k):
            s, e = first[j], first[j] + got.counts[j]
            parts.append((got.rows[s:e], got.cols[s:e], vals[s:e]))
        counts.append(got.counts)
    sess.state = dataclasses.replace(state, layers=tuple(layers))
    return parts, counts
