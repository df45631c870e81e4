#!/usr/bin/env python3
"""Smoke run of the served D4M store on one TPU chip.

    python chip_smoke.py [--seed N]      # one chip: runs (a) and (b)
    python chip_smoke.py --four-chips    # four chips: the mesh run only

Every run drives the real path: ``D4MStream.serve()`` over a loopback
``TCPSource``, records streamed through a ``QueryClient`` in the binary
wire encoding, ``top_k``/``degrees``/``row``/``get`` queries answered on
the same socket while ingest runs, drain, and a restore of the final
checkpoint into a fresh session.  Traffic is Graph500 R-MAT (a, b, c =
.57, .19, .19; unit weights; plus.times), generated on the host with numpy
from ``--seed`` before serving starts.  Every answer, the drained snapshot
and the restored snapshot are compared with a plain numpy dict-of-keys sum
of the same triples.

* Run (a), the K>1 served store: K=32 instances on the chip (one node's
  share of the paper's 34,000 instances on 1,100 nodes), engine ``auto``,
  cuts (1024, 8192), batch 512 and the largest top layer the chosen engine
  compiles at; enough records that every lane's every cut fires.
* Run (b), the paper's single instance: K=1 at ``configs/d4m_stream``
  (cuts 100K/1M/10M, top 140M, 100K-record groups, R-MAT scale 20), with
  enough records that the first two cuts fire.
* ``--four-chips``: run (a)'s cuts, batch and record count on
  ``devices=4, instances_per_device=8`` (the ``mesh`` engine, 32 lanes as
  in run (a); the top layer cut to 90,272 entries per lane to fit the
  time, printed with its reason), every lane's every cut firing, and
  where each state leaf's shards live.  The stream has run (a)'s seed,
  but is drawn alone, so its records differ from run (a)'s.

The script exits nonzero, printing no result, when JAX finds no TPU, and on
any mismatch.  Its last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_cache/`` in this checkout (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

RMAT_SCALE = 20  # Graph500 scale of configs/d4m_stream.CONFIG
QUERY_POINTS = (0.25, 0.5, 0.75)  # fractions of the stream sent before asking
FOUR_CHIP_TOP = 90_272  # per-lane top layer of the --four-chips run (see runs)


@dataclasses.dataclass(frozen=True)
class Run:
    name: str
    config: object  # d4m.StreamConfig
    records: int
    chunk: int  # records per client insert frame
    publish_every: int
    checkpoint_every: int
    must_fire: int  # cuts (from the first) that must fire on every lane


def fail(what: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED {what}")


def expect(cond, what: str) -> None:
    if not cond:
        fail(what)


def log(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


class CompileLog:
    """Backend compile seconds and persistent-cache traffic, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.secs, self.hits, self.writes = 0.0, 0, 0

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.writes += 1

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += duration

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def mark(self):
        return self.secs, self.hits, self.writes


# ---------------------------------------------------------------------------
# the numpy reference: a dict-of-keys sum over a prefix of the stream
# ---------------------------------------------------------------------------

class Reference:
    def __init__(self, rows, cols, vals):
        self.rows, self.cols, self.vals = rows, cols, vals
        self._memo = {}

    def dok(self, n: int):
        """Sorted unique keys ``row << 32 | col`` and their float32 sums."""
        if n not in self._memo:
            key = (self.rows[:n].astype(np.int64) << 32) | self.cols[:n].astype(np.int64)
            uk, inv = np.unique(key, return_inverse=True)
            sums = np.bincount(inv, weights=self.vals[:n], minlength=uk.size)
            self._memo = {n: (uk, sums.astype(np.float32))}
        return self._memo[n]

    def degrees(self, n: int, by: str):
        ids = (self.rows if by == "out" else self.cols)[:n]
        return np.bincount(ids, weights=self.vals[:n], minlength=1 << RMAT_SCALE)


def same_f32(a, b) -> bool:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return a.shape == b.shape and bool((a.view(np.uint32) == b.view(np.uint32)).all())


def check_snapshot(snap, ref: Reference, n: int, what: str) -> int:
    expect(not bool(snap.overflow), f"{what}: snapshot overflowed")
    nnz = int(snap.nnz)
    r = np.asarray(snap.rows)[:nnz].astype(np.int64)
    c = np.asarray(snap.cols)[:nnz].astype(np.int64)
    v = np.asarray(snap.vals)[:nnz]
    uk, sums = ref.dok(n)
    expect(nnz == uk.size, f"{what}: {nnz} keys, reference has {uk.size}")
    expect(np.array_equal((r << 32) | c, uk), f"{what}: keys differ from the reference")
    expect(same_f32(v, sums), f"{what}: values differ from the reference")
    return nnz


def check_reply(op: str, args: dict, rep, ref: Reference) -> None:
    what = f"query {op}{args} over view_records={rep.view_records}"
    expect(rep.ok, f"{what}: {rep.error}")
    n = int(rep.view_records)
    uk, sums = ref.dok(n)
    if op == "get":
        key = (args["r"] << 32) | args["c"]
        i = np.searchsorted(uk, key)
        want = sums[i] if i < uk.size and uk[i] == key else np.float32(0)
        expect(same_f32(rep.scalars["value"], want), f"{what}: {rep.scalars['value']} != {want}")
    elif op == "row":
        lo, hi = np.searchsorted(uk, [args["r"] << 32, (args["r"] + 1) << 32])
        expect(np.array_equal(rep.arrays["cols"], (uk[lo:hi] & 0xFFFFFFFF).astype(np.int32)),
               f"{what}: columns differ")
        expect(same_f32(rep.arrays["vals"], sums[lo:hi]), f"{what}: values differ")
    elif op == "degrees":
        for by in ("out", "in"):
            deg = ref.degrees(n, by)
            ids = np.flatnonzero(deg)
            expect(np.array_equal(rep.arrays[f"{by}_ids"], ids), f"{what}: {by} ids differ")
            expect(same_f32(rep.arrays[f"{by}_vals"], deg[ids]), f"{what}: {by} degrees differ")
    elif op == "top_k":
        deg = ref.degrees(n, args["by"])
        ids, vals = np.asarray(rep.arrays["ids"]), np.asarray(rep.arrays["vals"])
        want = np.sort(deg)[::-1][: args["k"]]
        live = want > 0
        expect(same_f32(vals[live], want[live]), f"{what}: top values differ")
        expect(same_f32(deg[ids[live]], vals[live]), f"{what}: ids do not carry their degrees")
    else:
        fail(f"unknown op {op}")


def ask_all(qc, ref: Reference, rows, cols, sent: int) -> int:
    """One of each query over the current view; returns replies checked."""
    j = sent // 2
    queries = [
        ("get", {"r": int(rows[j]), "c": int(cols[j])}),
        ("row", {"r": int(rows[j])}),
        ("degrees", {}),
        ("top_k", {"k": 10, "by": "out"}),
        ("top_k", {"k": 10, "by": "in"}),
    ]
    for op, args in queries:
        check_reply(op, args, qc.request(op, **args), ref)
    return len(queries)


# ---------------------------------------------------------------------------
# one served run
# ---------------------------------------------------------------------------

def serve_run(run: Run, rows, cols, vals, clog: CompileLog, workdir: str) -> None:
    import jax

    from repro import d4m, serve

    n = run.records
    r, c, v = rows[:n], cols[:n], vals[:n]
    ref = Reference(r, c, v)
    cfg = run.config
    plan = cfg.plan()
    ckpt = str(Path(workdir) / f"ckpt-{run.name}")
    c0 = clog.mark()
    t0 = time.perf_counter()
    sess = d4m.D4MStream(cfg, checkpoint_dir=ckpt, checkpoint_keep=2)
    log(run=run.name, engine=sess.kind, K=sess.k_per_device,
        devices=cfg.resolved_devices(), instances=sess.n_instances,
        cuts=list(plan.cuts), caps=list(plan.layer_caps), batch=cfg.batch_size,
        top_capacity=cfg.top_capacity, records=n, rmat_scale=RMAT_SCALE,
        state_bytes=plan.total_bytes)
    src = serve.TCPSource(port=0, encoding="binary", linger=False)
    server = serve.D4MServer(sess, src, d4m.ServeConfig(
        publish_every=run.publish_every,
        checkpoint_every=run.checkpoint_every,
        drain_timeout_s=900.0,
    )).start()
    answered = 0
    marks = {int(f * n) // run.chunk * run.chunk for f in QUERY_POINTS}
    with serve.QueryClient("127.0.0.1", src.port, encoding="binary", timeout_s=900.0) as qc:
        for lo in range(0, n, run.chunk):
            qc.insert(r[lo:lo + run.chunk], c[lo:lo + run.chunk], v[lo:lo + run.chunk])
            if lo + run.chunk in marks:
                answered += ask_all(qc, ref, r, c, lo + run.chunk)
        # views publish on microbatch boundaries, so this one covers a prefix
        answered += ask_all(qc, ref, r, c, n)
    expect(server.join(timeout=900.0), f"run {run.name}: serve did not drain")
    report = server.report()
    wall = time.perf_counter() - t0
    expect(report.drained and report.records_fed == n,
           f"run {run.name}: fed {report.records_fed} of {n}")
    expect(report.records_dropped == 0 and report.malformed == 0,
           f"run {run.name}: dropped {report.records_dropped}, malformed {report.malformed}")
    tel = sess.telemetry()
    # [instances, layers] cascade counters (one row for the single engine)
    casc = np.atleast_2d(np.asarray(
        tel.cascades if sess.kind == "single" else tel.cascades_per_instance))
    expect(not tel.overflowed, f"run {run.name}: the state overflowed")
    expect((casc[:, 1:1 + run.must_fire] > 0).all(),
           f"run {run.name}: a lane's cut never fired: {casc.tolist()}")
    nnz = check_snapshot(sess.snapshot(), ref, n, f"run {run.name} drained")
    layout = None
    if sess.kind == "mesh":
        layout = {
            jax.tree_util.keystr(path): sorted({s.device.id for s in leaf.addressable_shards})
            for path, leaf in jax.tree_util.tree_leaves_with_path(sess.state)
        }
        spread = {tuple(ids) for ids in layout.values()}
        expect(spread == {tuple(range(len(jax.devices())))},
               f"run {run.name}: state not on every device: {layout}")
    c1 = clog.mark()
    del server, sess
    gc.collect()

    # a fresh session restored from the final checkpoint answers the same
    t1 = time.perf_counter()
    fresh = d4m.D4MStream(cfg, checkpoint_dir=ckpt)
    extra = fresh.restore()
    expect(extra.get("cursor") == n and extra.get("final"),
           f"run {run.name}: restored cursor {extra}")
    check_snapshot(fresh.snapshot(), ref, n, f"run {run.name} restored")
    restore_s = time.perf_counter() - t1
    del fresh
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    log(run=run.name, check="drained+restored+queries match numpy reference",
        nnz=nnz, queries=answered, batches=report.batches_fed,
        cascades_per_layer=casc.sum(axis=0).tolist(),
        min_lane_cascades_per_layer=casc.min(axis=0).tolist(),
        checkpoints=len(report.checkpoints), wall_s=round(wall, 3),
        restore_s=round(restore_s, 3), ingest_rate=round(report.ingest_rate, 1),
        compile_s=round(c1[0] - c0[0], 3), cache_hits=c1[1] - c0[1],
        cache_writes=c1[2] - c0[2],
        # JAX reports one peak for the whole process, not one per run
        process_peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))
    if layout is not None:
        for leaf, ids in layout.items():
            log(run=run.name, leaf=leaf, devices=ids)


def runs(four_chips: bool):
    from repro import d4m
    from repro.configs import d4m_stream
    from repro.kernels.hier_cascade import kernel as cascade_kernel

    cuts_a, batch_a, k_a = (1024, 8192), 512, 32
    engine = d4m.StreamConfig(
        cuts=cuts_a, top_capacity=1, batch_size=batch_a, instances_per_device=k_a
    ).resolved_engine()
    cap2 = cuts_a[0] + batch_a + cuts_a[1]
    if engine == "pallas":
        # the largest top layer whose merge still fits the kernel's VMEM
        width = 1 << 24
        while cascade_kernel.vmem_bytes([width]) > cascade_kernel.VMEM_BUDGET_BYTES:
            width //= 2
        top_a = width - cap2
    else:
        top_a = 200_000
    # 400,000 records make every lane's every cut fire
    served = dict(records=400_000, chunk=4096, publish_every=200,
                  checkpoint_every=500, must_fire=len(cuts_a))
    if four_chips:
        # The mesh runs the branchless packed step, which merges every
        # layer of every lane on every step: 2.7 us per layer entry per
        # device on a v5e (5.67 s a step at run (a)'s top), so run (a)'s
        # 782 steps would take 74 min.  A top of 90,272 (100,000 with the
        # lower layers) keeps the run near five minutes on four v5e chips;
        # cuts, batch and record count stay run (a)'s.
        log(run="a4", cut=f"top_capacity {top_a} -> {FOUR_CHIP_TOP}",
            reason="mesh step 5.67 s at the full top on v5e; 782 steps = 74 min")
        return [Run("a4", d4m.StreamConfig(
            cuts=cuts_a, top_capacity=FOUR_CHIP_TOP, batch_size=batch_a,
            instances_per_device=k_a // 4, devices=4, snapshot_cap=400_000,
        ), **served)]
    paper = d4m_stream.CONFIG
    return [
        Run("a", d4m.StreamConfig(
            cuts=cuts_a, top_capacity=top_a, batch_size=batch_a,
            instances_per_device=k_a, snapshot_cap=400_000,
        ), **served),
        Run("b", paper.to_session(snapshot_cap=2_000_000),
            records=2_000_000, chunk=100_000, publish_every=10,
            checkpoint_every=15, must_fire=2),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the served stream on 4 chips (mesh engine)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chips, found {len(devices)}", file=sys.stderr)
        return 1

    from repro import compile_cache
    from repro.data.rmat import rmat_edges_np

    cache = compile_cache.enable()
    clog = CompileLog()
    log(device_kind=devices[0].device_kind, device_count=len(devices),
        jax=jax.__version__, compile_cache=cache)
    todo = runs(args.four_chips)
    total = max(run.records for run in todo)
    t = time.perf_counter()
    rows, cols = rmat_edges_np(np.random.default_rng(args.seed), total, RMAT_SCALE)
    vals = np.ones(total, np.float32)
    log(data="rmat", records=total, scale=RMAT_SCALE, seed=args.seed,
        gen_s=round(time.perf_counter() - t, 3))
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as workdir:
        for run in todo:
            serve_run(run, rows, cols, vals, clog, workdir)
    secs, hits, writes = clog.mark()
    log(compile_s_total=round(secs, 3), cache_hits_total=hits, cache_writes_total=writes)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
