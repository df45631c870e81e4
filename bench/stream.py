"""The benchmark's traffic: seeded Graph500 R-MAT records and their wire frames.

Pure numpy, so the load generator (a process of its own that must never
touch JAX) and the harness's reference draw the very same stream from one
``--seed``:

* records come in chunks; chunk ``i`` is drawn from its own
  ``SeedSequence([seed, i])``, so any prefix of the stream can be rebuilt
  without replaying the timing that produced it;
* edges are Graph500 R-MAT (quadrant probabilities a, b, c; d = 1 - a - b
  - c) over ``2**scale`` vertices, relabelled by Graph500's seeded vertex
  permutation so the heavy vertices are not the low ids;
* weights are uniform in (0, 1], like Graph500's SSSP edge weights.  Sums of
  such weights round differently in every precision, so a run that kept
  its values in bfloat16 cannot pass for one that kept float32.

The insert frame encoder is a copy of the ``D4MF`` v1 wire format of
``repro.serve.wire``, kept here so the generator imports nothing of the
program.
"""
from __future__ import annotations

import struct
from typing import Any, Mapping, Tuple

import numpy as np

SEED_MOD = 1 << 64  # seeds of any size map onto numpy's SeedSequence
_PERM_TAG = 0x5045524D  # stream tag of the vertex permutation ("PERM")


def _seq(seed: int, *tags: int) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed) % SEED_MOD, *tags])


def vertex_permutation(seed: int, scale: int) -> np.ndarray:
    """Graph500's vertex relabelling: a seeded permutation of ``2**scale``."""
    rng = np.random.default_rng(_seq(seed, _PERM_TAG))
    return rng.permutation(1 << scale).astype(np.int32)


def rmat_chunk(
    seed: int,
    index: int,
    n: int,
    scale: int,
    perm: np.ndarray,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Chunk ``index`` of the stream: ``n`` permuted R-MAT edges with
    uniform (0, 1] float32 weights, as int32 rows, int32 cols, float32 vals."""
    rng = np.random.default_rng(_seq(seed, int(index)))
    r = rng.random((scale, n))
    src_bits = r >= a + b  # quadrants c, d
    dst_bits = ((r >= a) & (r < a + b)) | (r >= a + b + c)
    place = (1 << np.arange(scale - 1, -1, -1, dtype=np.int64))[:, None]
    src = (src_bits * place).sum(axis=0)
    dst = (dst_bits * place).sum(axis=0)
    vals = (1.0 - rng.random(n)).astype(np.float32)  # (0, 1]
    return perm[src], perm[dst], vals


class Stream:
    """The record stream of one run: fixed-size chunks drawn from the seed."""

    def __init__(self, seed: int, rmat: Mapping[str, Any], chunk_records: int):
        self.seed = int(seed)
        self.scale = int(rmat["scale"])
        self.abc = (float(rmat["a"]), float(rmat["b"]), float(rmat["c"]))
        self.chunk_records = int(chunk_records)
        self.perm = vertex_permutation(self.seed, self.scale)

    def chunk(self, index: int):
        return rmat_chunk(
            self.seed, index, self.chunk_records, self.scale, self.perm, *self.abc
        )

    def prefix(self, n: int):
        """The first ``n`` records (whole chunks, cut to ``n``)."""
        k = -(-int(n) // self.chunk_records)
        parts = [self.chunk(i) for i in range(k)]
        if not parts:
            return (np.zeros(0, np.int32), np.zeros(0, np.int32), np.zeros(0, np.float32))
        return tuple(np.concatenate([p[j] for p in parts])[: int(n)] for j in range(3))


# ---------------------------------------------------------------------------
# D4MF v1 insert frames (a copy of repro.serve.wire's binary encoding)
# ---------------------------------------------------------------------------

FRAME_MAGIC = b"D4MF"
PROTOCOL_VERSION = 1
OP_INSERT = 0x01
_V1_HEADER = struct.Struct("<4sBBHI")  # magic, version, op, reserved, body len


def encode_insert(rows, cols, vals) -> bytes:
    """One INSERT frame: ``count u32`` + int32 rows + int32 cols + float32 vals."""
    rows = np.ascontiguousarray(rows, np.int32)
    cols = np.ascontiguousarray(cols, np.int32)
    vals = np.ascontiguousarray(vals, np.float32)
    if not rows.shape == cols.shape == vals.shape or rows.ndim != 1:
        raise ValueError(f"triple columns disagree: {rows.shape} {cols.shape} {vals.shape}")
    body = struct.pack("<I", rows.shape[0]) + rows.tobytes() + cols.tobytes() + vals.tobytes()
    return _V1_HEADER.pack(FRAME_MAGIC, PROTOCOL_VERSION, OP_INSERT, 0, len(body)) + body
