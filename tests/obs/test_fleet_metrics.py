"""Fleet-wide metrics conservation: real subprocess workers.

The acceptance property: the controller's merged scrape
(``FleetController.metrics()``) conserves every worker's event counts
exactly — the merged ``serve.update_dispatch_ns`` histogram carries
precisely the sum of the per-worker bucket counts, and that total equals
the fleet's ``batches_fed`` counter (one dispatch per fed batch, across
process boundaries and a JSON control channel).

Sized for a 1-core CI box: 2 workers, ~1k records.
"""
import numpy as np

from repro import d4m, serve
from repro.fleet import FleetController
from repro.obs import hist as obs_hist

TOTAL = 1024
CAP = 8192
_ENV = {
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    "OMP_NUM_THREADS": "1",
    "OPENBLAS_NUM_THREADS": "1",
}


def _config():
    return d4m.StreamConfig(
        cuts=(256, 1024), top_capacity=4096, batch_size=128,
        instances_per_device=2, snapshot_cap=CAP,
    )


def _records(seed=13):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, 4096, TOTAL).astype(np.int32),
        rng.integers(0, 4096, TOTAL).astype(np.int32),
        rng.integers(1, 8, TOTAL).astype(np.float32),
    )


def test_fleet_metrics_scrape_conserves_counts(tmp_path):
    rows, cols, vals = _records()
    ctl = FleetController(
        _config(), n_workers=2, workdir=str(tmp_path / "fleet"),
        serve_config=d4m.ServeConfig(drain_timeout_s=600.0),
        report_interval_s=0.2, env=_ENV, metrics=True,
        heartbeat_timeout_s=60.0,  # arms the heartbeat-age gauges
    )
    report = ctl.run(
        serve.ArraySource(rows, cols, vals, chunk_records=256),
        finish_timeout_s=600,
    )
    assert report.conserved and report.records_in == TOTAL

    # every worker piggybacked its final registry dump on the report
    dumps = [h.metrics_dump for h in ctl.workers]
    assert all(d is not None for d in dumps)

    merged = ctl.metrics()
    assert merged is not None
    name = "serve.update_dispatch_ns"
    per_worker = [obs_hist.state_count(d["histograms"][name]) for d in dumps]
    assert all(n > 0 for n in per_worker)
    merged_st = merged["histograms"][name]
    # exact conservation: merged bucket counts == sum of worker counts ...
    assert obs_hist.state_count(merged_st) == sum(per_worker)
    np.testing.assert_array_equal(
        np.asarray(merged_st["counts"]),
        np.sum([d["histograms"][name]["counts"] for d in dumps], axis=0),
    )
    assert merged_st["max_ns"] == max(
        d["histograms"][name]["max_ns"] for d in dumps
    )
    # ... and the distribution total equals the fleet's batch counter:
    # one dispatch per fed batch, across process boundaries
    assert obs_hist.state_count(merged_st) == int(report.telemetry.batches_fed)

    # the controller's own push-latency histogram joined the merge
    assert obs_hist.state_count(merged["histograms"]["fleet.push_ns"]) > 0

    # merged TelemetrySnapshot carries the same conservation
    tel_hist = report.telemetry.histograms
    assert tel_hist is not None
    assert obs_hist.state_count(tel_hist[name]) == sum(per_worker)

    # heartbeat-age gauges exist for every worker slot
    hb = [k for k in merged["gauges"] if k.startswith("fleet.heartbeat_age_s")]
    assert len(hb) == 2
