"""The plain reference, the comparison that decides ``correct``, and its control."""
import numpy as np
import pytest

import reference
import stream


def test_dok_sums_a_known_stream():
    rows = np.array([1, 2, 1, 1, 3], np.int32)
    cols = np.array([5, 5, 5, 6, 0], np.int32)
    vals = np.array([0.5, 0.25, 0.125, 1.0, 0.75], np.float32)
    keys, sums = reference.dok(rows, cols, vals)
    assert keys.tolist() == [(1 << 32) | 5, (1 << 32) | 6, (2 << 32) | 5, (3 << 32) | 0]
    assert sums.tolist() == [0.625, 1.0, 0.25, 0.75]


def test_state_dok_sums_layers_and_compare_counts_wrong_keys():
    want = reference.dok(np.array([1, 1, 2]), np.array([1, 2, 2]), np.array([1.0, 2.0, 3.0]))
    # the same key held in two layers sums; a missing key and an extra key
    # are two wrong keys
    layers = [
        (np.array([1, 2]), np.array([1, 2]), np.array([0.25, 3.0], np.float32)),
        (np.array([1, 9]), np.array([1, 9]), np.array([0.75, 1.0], np.float32)),
    ]
    got = reference.compare_state(reference.state_dok(layers), want)
    assert got == {"keys_wrong": 2, "value_rel_err": 0.0}
    off = [(np.array([1, 1, 2]), np.array([1, 2, 2]), np.array([1.0, 2.0, 3.003], np.float32))]
    got = reference.compare_state(reference.state_dok(off), want)
    assert got["keys_wrong"] == 0 and got["value_rel_err"] == pytest.approx(1e-3, rel=1e-3)


def test_judge_puts_every_number_beside_its_limit():
    numbers = {k: 0 for k in reference.LIMITS}
    ok, checks = reference.judge(numbers)
    assert ok and list(checks) == list(reference.LIMITS)
    assert checks["value_rel_err"] == {"value": 0, "limit": reference.LIMITS["value_rel_err"]}
    for bad in ({"keys_wrong": 1}, {"value_rel_err": 1e-3}, {"window_compiles": 2},
                {"value_rel_err": float("inf")}):
        assert not reference.judge({**numbers, **bad})[0]


def test_reference_starts_from_the_prefilled_state():
    """The window's records add to the state the window started from, key by
    key, whether a key sits in one layer, in two, or only in the records."""
    base = [
        (np.array([1, 4], np.int32), np.array([1, 4], np.int32), np.array([0.5, 2.0], np.float32)),
        (np.array([1], np.int32), np.array([1], np.int32), np.array([0.25], np.float32)),
    ]
    rows, cols, vals = np.array([1, 7], np.int32), np.array([1, 7], np.int32), np.array([1.0, 3.0], np.float32)
    keys, sums = reference.dok_from(base, rows, cols, vals)
    assert keys.tolist() == [(1 << 32) | 1, (4 << 32) | 4, (7 << 32) | 7]
    assert sums.tolist() == [1.75, 2.0, 3.0]
    ctl_keys, _ = reference.dok_bfloat16(base, rows, cols, vals)
    assert ctl_keys.tolist() == keys.tolist()


def _records(n, seed=21):
    return stream.Stream(seed, {"scale": 20, "a": 0.57, "b": 0.19, "c": 0.19}, 4096).prefix(n)


def test_float32_sums_pass_and_the_bfloat16_control_fails():
    """The control (the reference summed in bfloat16 in the program's place)
    fails ``value_rel_err``; float32 sums in stream order, the precision the
    configurations state, pass it."""
    rows, cols, vals = _records(60_000)
    want = reference.dok(rows, cols, vals)
    key = reference.keys_of(rows, cols)
    order = np.argsort(key, kind="stable")
    uk, start = np.unique(key[order], return_index=True)
    f32 = np.add.reduceat(vals[order].astype(np.float32), start, dtype=np.float32)
    limit = reference.LIMITS["value_rel_err"]
    assert reference.compare_state((uk, f32.astype(np.float64)), want)["value_rel_err"] < limit / 10
    ctl = reference.compare_state(reference.dok_bfloat16([], rows, cols, vals), want)
    assert ctl["keys_wrong"] == 0 and ctl["value_rel_err"] > 100 * limit
