"""Fleet chaos: crash, crash-loop, hang, and journal failure against real
subprocess workers, each asserted against its invariant class.

* recoverable faults (one crash, one hang — scoped to generation 0 so the
  revival runs clean) must drain to a merged snapshot **bit-identical** to
  single-process ingest, with nothing lost or double-folded;
* unrecoverable faults (a worker that crashes in every incarnation, a
  journal that rejects an append) must end with **exact accounting**:
  ``records_delivered + records_quarantined == records_in``, the
  quarantined key-range surfaced, and ``merged_snapshot`` refusing rather
  than returning silently-partial state.

Sized like tests/fleet (same StreamConfig, so the workers share the
suite's persistent compilation cache).
"""
import json
import os

import numpy as np
import pytest

from repro import d4m, serve
from repro.faults import FaultPlan, Trigger
from repro.fleet import FleetController
from repro.fleet.routing import host_key_range

TOTAL = 2048
CHUNK = 256
CAP = 8192

_ENV = {
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    "OMP_NUM_THREADS": "1",
    "OPENBLAS_NUM_THREADS": "1",
}
_SERVE = dict(drain_timeout_s=600.0)


def _seeds():
    with open(os.path.join(os.path.dirname(__file__), "seeds.json")) as f:
        return json.load(f)


def _config() -> d4m.StreamConfig:
    return d4m.StreamConfig(
        cuts=(256, 1024),
        top_capacity=4096,
        batch_size=128,
        instances_per_device=2,
        snapshot_cap=CAP,
    )


def _records(total: int = TOTAL, seed: int = None):
    rng = np.random.default_rng(
        seed if seed is not None else _seeds()["fleet_seed"]
    )
    rows = rng.integers(0, 4096, total).astype(np.int32)
    cols = rng.integers(0, 4096, total).astype(np.int32)
    vals = rng.integers(1, 8, total).astype(np.float32)
    return rows, cols, vals


def _reference_snapshot(rows, cols, vals):
    sess = d4m.D4MStream(_config())
    for lo in range(0, rows.shape[0], 128):
        dropped = sess.ingest(
            rows[lo:lo + 128], cols[lo:lo + 128], vals[lo:lo + 128]
        )
        assert int(dropped) == 0
    return sess.snapshot(cap=CAP)


def _assert_bit_identical(snap, ref):
    nnz = int(ref.nnz)
    assert int(snap.nnz) == nnz
    np.testing.assert_array_equal(np.asarray(snap.rows)[:nnz],
                                  np.asarray(ref.rows)[:nnz])
    np.testing.assert_array_equal(np.asarray(snap.cols)[:nnz],
                                  np.asarray(ref.cols)[:nnz])
    np.testing.assert_array_equal(np.asarray(snap.vals)[:nnz],
                                  np.asarray(ref.vals)[:nnz])


def test_crash_in_generation_zero_recovers_bit_identical(
    tmp_path, chaos_record
):
    """worker.crash_after_n_batches scoped to generation 0: the victim
    hard-exits mid-stream (no unwind, no final checkpoint), the controller
    revives it from the last acked checkpoint (or fresh), replays the
    journal tail, and the drained fleet is bit-identical to single-process
    ingest."""
    rows, cols, vals = _records()
    faults = FaultPlan().add(
        "worker.crash_after_n_batches", Trigger.once_at(4),
        only_worker=1, only_generation=0,
    )
    ctl = FleetController(
        _config(), n_workers=2, workdir=str(tmp_path / "fleet"),
        serve_config=d4m.ServeConfig(checkpoint_every=2, **_SERVE),
        report_interval_s=0.1, env=_ENV, faults=faults,
    )
    report = ctl.run(
        serve.ArraySource(rows, cols, vals, chunk_records=CHUNK),
        finish_timeout_s=600,
    )
    assert report.restarts == 1, "one crash, one clean revival"
    assert not report.quarantined
    assert report.conserved
    assert report.records_in == TOTAL
    assert report.records_delivered == TOTAL
    assert ctl.workers[1].generation == 1
    _assert_bit_identical(
        report.merged_snapshot(cap=CAP),
        _reference_snapshot(rows, cols, vals),
    )
    chaos_record("worker.crash_after_n_batches", invariant="bit_identical",
                 seed=_seeds()["fleet_seed"], restarts=report.restarts)


def test_crash_loop_ends_quarantined_with_exact_accounting(
    tmp_path, chaos_record
):
    """An unscoped crash spec re-fires in every incarnation: after
    max_restarts_per_worker failed revivals the slot is quarantined, its
    key-range and journaled-but-undelivered count surface in the report,
    the ledger still balances exactly, and merged_snapshot refuses."""
    rows, cols, vals = _records(seed=7)
    faults = FaultPlan().add(
        "worker.crash_after_n_batches", Trigger.nth(1), only_worker=1,
    )
    ctl = FleetController(
        _config(), n_workers=2, workdir=str(tmp_path / "fleet"),
        serve_config=d4m.ServeConfig(**_SERVE),
        report_interval_s=0.1, env=_ENV, faults=faults,
        max_restarts_per_worker=2,
    )
    with ctl:
        for lo in range(0, TOTAL, CHUNK):
            ctl.push(rows[lo:lo + CHUNK], cols[lo:lo + CHUNK],
                     vals[lo:lo + CHUNK])
            ctl.poll_workers()
        report = ctl.finish(timeout_s=600)

    assert len(report.quarantined) == 1
    q = report.quarantined[0]
    assert q["worker"] == 1
    assert (q["key_hash_lo"], q["key_hash_hi"]) == host_key_range(1, 2)
    assert q["restarts"] == 2, "every allowed revival was burned"
    assert q["journaled"] == ctl.workers[1].journal.total
    assert q["undelivered"] == q["journaled"] - q["delivered"]
    assert report.records_quarantined == q["undelivered"]
    assert report.records_quarantined > 0
    assert report.per_worker[1]["quarantined"] is True
    # the ledger balances to the record: every routed record is either
    # delivered by the live worker or accounted against the quarantine
    assert report.conserved
    assert report.records_in == TOTAL
    assert (report.records_delivered + report.records_quarantined == TOTAL)
    # partial state must be refused, not silently returned
    with pytest.raises(RuntimeError, match="quarantined"):
        report.merged_snapshot(cap=CAP)
    chaos_record("worker.crash_after_n_batches",
                 invariant="exact_accounting", seed=7,
                 quarantined=report.records_quarantined,
                 delivered=report.records_delivered)


def test_hung_worker_detected_by_heartbeat_and_recovered(
    tmp_path, chaos_record
):
    """worker.hang scoped to generation 0: the process stays alive with
    every socket open but stops reporting; only the heartbeat deadline can
    see it.  The controller SIGKILLs and revives it, and the fleet drains
    bit-identical."""
    rows, cols, vals = _records(seed=5)
    faults = FaultPlan().add(
        "worker.hang", Trigger.nth(1), only_worker=1, only_generation=0,
    )
    ctl = FleetController(
        _config(), n_workers=2, workdir=str(tmp_path / "fleet"),
        serve_config=d4m.ServeConfig(checkpoint_every=2, **_SERVE),
        report_interval_s=0.1, env=_ENV, faults=faults,
        # healthy cadence is one control message per 0.1s; the deadline
        # arms at each incarnation's hello (startup compile is off the
        # clock), so 20s is ~200x margin against CPU-contention stalls
        # while still detecting the hang promptly
        heartbeat_timeout_s=20.0,
    )
    report = ctl.run(
        serve.ArraySource(rows, cols, vals, chunk_records=CHUNK),
        finish_timeout_s=600,
    )
    assert report.restarts >= 1, "the hang must be detected as a death"
    assert not report.quarantined
    assert report.conserved
    assert report.records_in == TOTAL
    assert report.records_delivered == TOTAL
    _assert_bit_identical(
        report.merged_snapshot(cap=CAP),
        _reference_snapshot(rows, cols, vals),
    )
    chaos_record("worker.hang", invariant="bit_identical", seed=5,
                 restarts=report.restarts)


def test_journal_disk_full_rejects_before_any_send(tmp_path, chaos_record):
    """controller.journal_disk_full: the append raises *before* the part
    is counted or sent, so records_in counts only accepted records and the
    ledger still balances — the fleet never claims records it could not
    journal."""
    rows, cols, vals = _records(seed=3)
    faults = FaultPlan().add(
        "controller.journal_disk_full", Trigger.once_at(600),
    )
    ctl = FleetController(
        _config(), n_workers=2, workdir=str(tmp_path / "fleet"),
        serve_config=d4m.ServeConfig(**_SERVE),
        report_interval_s=0.1, env=_ENV, faults=faults,
    )
    rejected = 0
    with ctl:
        for lo in range(0, TOTAL, CHUNK):
            try:
                ctl.push(rows[lo:lo + CHUNK], cols[lo:lo + CHUNK],
                         vals[lo:lo + CHUNK])
            except OSError:
                rejected += 1
        report = ctl.finish(timeout_s=600)

    assert rejected == 1, "the once_at spec rejects exactly one append"
    assert faults.summary()["controller.journal_disk_full"]["fires"] == 1
    assert report.records_in < TOTAL, "rejected records are not counted"
    assert report.conserved
    assert report.records_delivered == report.records_in
    assert not report.quarantined
    chaos_record("controller.journal_disk_full",
                 invariant="exact_accounting", seed=3,
                 accepted=report.records_in, rejected_pushes=rejected)
