"""The load generator's stream: seeded, permuted, weighted, framed like the wire."""
import numpy as np
import pytest

import stream
from repro.serve import wire

RMAT = {"scale": 20, "a": 0.57, "b": 0.19, "c": 0.19}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 17, 2**40 + 3])
def test_stream_is_a_function_of_the_seed(seed):
    a, b = stream.Stream(seed, RMAT, 4096), stream.Stream(seed, RMAT, 4096)
    for i in (0, 5):
        for x, y in zip(a.chunk(i), b.chunk(i)):
            np.testing.assert_array_equal(x, y)
    other = stream.Stream(seed + 1, RMAT, 4096).chunk(0)
    assert not np.array_equal(a.chunk(0)[0], other[0])
    # chunks differ from one another, and a prefix is whole chunks cut short
    assert not np.array_equal(a.chunk(0)[0], a.chunk(1)[0])
    r, c, v = a.prefix(5000)
    np.testing.assert_array_equal(r[4096:], a.chunk(1)[0][:904])
    assert r.dtype == c.dtype == np.int32 and v.dtype == np.float32


def test_vertex_permutation_and_weights():
    perm = stream.vertex_permutation(11, 12)
    assert np.array_equal(np.sort(perm), np.arange(1 << 12))
    assert not np.array_equal(perm, np.arange(1 << 12))
    assert np.array_equal(perm, stream.vertex_permutation(11, 12))
    ident = np.arange(1 << 20, dtype=np.int32)
    r, c, v = stream.rmat_chunk(3, 0, 200_000, 20, ident)
    # Graph500 quadrants: the top id bit is set on the row with c + d = 0.24
    # and on the column with b + d = 0.24
    assert abs(np.mean(r >> 19) - 0.24) < 0.01
    assert abs(np.mean(c >> 19) - 0.24) < 0.01
    assert v.min() > 0 and v.max() <= 1 and abs(v.mean() - 0.5) < 0.01
    # the permutation relabels the very same edges
    perm = stream.vertex_permutation(3, 20)
    rp, cp, vp = stream.rmat_chunk(3, 0, 200_000, 20, perm)
    np.testing.assert_array_equal(rp, perm[r])
    np.testing.assert_array_equal(cp, perm[c])
    np.testing.assert_array_equal(vp, v)


def test_frames_are_byte_equal_to_the_wire():
    r, c, v = stream.Stream(5, RMAT, 1000).chunk(0)
    assert stream.encode_insert(r, c, v) == wire.encode_binary(r, c, v, version=1)
    # and the server's own decoder reads our insert frames back unchanged
    msgs, rest, bad = wire.decode_messages(stream.encode_insert(r, c, v))
    assert not rest and not bad
    np.testing.assert_array_equal(msgs[0][1][2], v)
