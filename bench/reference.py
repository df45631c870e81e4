"""The plain reference and the comparison that decides ``correct``.

The store's semantics under ``plus.times``: the value at a key ``(row,
col)`` is the sum of the weights of every record with that key.  The
reference is a dict-of-keys sum in numpy, in float64, of the state the
window started from (the benchmark's prefill, read back before the window)
and exactly the records the server applied; it imports nothing of the
program.

The drained state is read back layer by layer (every lane, every layer,
live slots only) and its layers are summed in float64 per key.  The
comparison gives these numbers, each with a limit (``LIMITS``):

* ``keys_wrong``: keys the state holds that the reference does not, plus
  keys the reference holds that the state does not (exact: 0);
* ``value_rel_err``: the largest ``|state - reference| / reference`` over
  the keys (weights are in (0, 1], so no reference value is 0);
* ``records_lost``: records the generator sent that the server did not
  apply, dropped or took for malformed (exact: 0);
* ``overflowed``: 1 when any layer of any lane overflowed (exact: 0);
* ``window_compiles``: programs compiled or loaded inside the measured
  window (exact: 0);
* ``window_empty``: 1 when the window applied no record (exact: 0).

The control puts the reference computed in bfloat16 (each key's running
sum rounded to bfloat16 after every entry of the starting state and every
record, in that order) in the program's place; it has to fail
``value_rel_err``.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np

# Limits of the compared numbers: a number passes when it is at most its
# limit.  value_rel_err's limit lies between the largest reading of sound
# runs and the smallest reading of the bfloat16 control; PERF.md gives both.
LIMITS = {
    "keys_wrong": 0,
    "value_rel_err": 1e-5,
    "records_lost": 0,
    "overflowed": 0,
    "window_compiles": 0,
    "window_empty": 0,
}


def keys_of(rows: np.ndarray, cols: np.ndarray) -> np.ndarray:
    return (rows.astype(np.int64) << 32) | (cols.astype(np.int64) & 0xFFFFFFFF)


def dok(rows, cols, vals) -> Tuple[np.ndarray, np.ndarray]:
    """Sorted unique keys and their float64 sums."""
    uk, inv = np.unique(keys_of(rows, cols), return_inverse=True)
    return uk, np.bincount(inv, weights=np.asarray(vals, np.float64), minlength=uk.size)


def _joined(base, rows, cols, vals):
    """The starting state's live entries followed by the records."""
    parts = [p for p in base if p[0].size] + [(rows, cols, vals)]
    return (np.concatenate([p[0] for p in parts]), np.concatenate([p[1] for p in parts]),
            np.concatenate([np.asarray(p[2], np.float64) for p in parts]))


def dok_from(base, rows, cols, vals) -> Tuple[np.ndarray, np.ndarray]:
    """The reference: the starting state ``base`` (live ``(rows, cols,
    vals)`` slices) plus the records, summed per key in float64."""
    return dok(*_joined(base, rows, cols, vals))


def dok_bfloat16(base, rows, cols, vals) -> Tuple[np.ndarray, np.ndarray]:
    """The control: each key's sum accumulated in bfloat16, the starting
    state's entries first, then the records in stream order."""
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16
    rows, cols, vals = _joined(base, rows, cols, vals)
    key = keys_of(rows, cols)
    order = np.argsort(key, kind="stable")  # stream order within a key
    ks = key[order]
    vs = np.asarray(vals, np.float32)[order].astype(bf16)
    uk, start, count = np.unique(ks, return_index=True, return_counts=True)
    acc = vs[start].copy()
    for j in range(1, int(count.max(initial=1))):
        more = count > j
        acc[more] = (acc[more].astype(np.float32) + vs[start[more] + j].astype(np.float32)).astype(bf16)
    return uk, acc.astype(np.float64)


def state_dok(layers: Iterable[Tuple[np.ndarray, np.ndarray, np.ndarray]]):
    """Sum the live ``(rows, cols, vals)`` slices of every layer per key."""
    parts = [p for p in layers if p[0].size]
    if not parts:
        return np.zeros(0, np.int64), np.zeros(0, np.float64)
    rows = np.concatenate([p[0] for p in parts])
    cols = np.concatenate([p[1] for p in parts])
    vals = np.concatenate([np.asarray(p[2], np.float64) for p in parts])
    return dok(rows, cols, vals)


def compare_state(got: Tuple[np.ndarray, np.ndarray], want: Tuple[np.ndarray, np.ndarray]) -> Dict[str, float]:
    """``keys_wrong`` and ``value_rel_err`` of a state against the reference."""
    gk, gv = got
    wk, wv = want
    if np.array_equal(gk, wk):
        err = float(np.max(np.abs(gv - wv) / np.abs(wv))) if wk.size else 0.0
        return {"keys_wrong": 0, "value_rel_err": err}
    common, gi, wi = np.intersect1d(gk, wk, assume_unique=True, return_indices=True)
    wrong = int(gk.size - common.size + wk.size - common.size)
    if common.size:
        err = float(np.max(np.abs(gv[gi] - wv[wi]) / np.abs(wv[wi])))
    else:
        err = 0.0 if not (gk.size or wk.size) else float("inf")
    return {"keys_wrong": wrong, "value_rel_err": err}


def judge(numbers: Dict[str, float], limits: Dict[str, float] = LIMITS) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every number beside its limit, and whether all pass."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return bool(ok), checks
