"""Fleet benchmark: aggregate served rate vs worker-process count.

The paper's headline number is an *aggregate*: 1.9B updates/s is 34,000
independent D4M instances behind hierarchical routing, not one fast node.
This bench measures our fleet tier the same way — a hosts × K sweep where
each point spawns ``hosts`` worker subprocesses (each running the full
``repro.serve`` ingress stack over K packed instances), routes one R-MAT
stream across them with the two-level hash router, and reports

* **aggregate rate** — unique source records over the controller's wall
  clock (start-of-feed to last worker report), per fleet size;
* **per-worker rates** and the conservation verdict (every routed record
  delivered exactly once — ``FleetReport.conserved``);
* the **fleet_scaling verdict**: aggregate rate at N workers >=
  ``EFFICIENCY_FLOOR`` x N x single-worker rate, gated at the largest N
  the hardware can actually parallelize (``N <= usable_cores``) — on a
  many-core CI box that is the paper-shaped "N workers ~ N x one worker"
  claim; on a starved box (cores < every multi-host point) the verdict
  degrades to the N=1 leg so it never fails for lack of silicon, while
  the full rates-vs-hosts curve is still recorded for the trend gate.

Emits ``BENCH_fleet.json`` on the standard reporting schema, so the trend
gate tracks the rates and the verdict automatically.
"""
from __future__ import annotations

import argparse
import os
import tempfile

from benchmarks.reporting import BenchmarkReport
from repro import d4m, serve
from repro.fleet import FleetController

EFFICIENCY_FLOOR = 0.7  # aggregate(N) >= floor * min(N, cores) * aggregate(1)


def _usable_cores() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - non-Linux
        return os.cpu_count() or 1


def _config(k: int, batch: int, top: int) -> d4m.StreamConfig:
    return d4m.StreamConfig(
        cuts=(2 * batch, 16 * batch),
        top_capacity=top,
        batch_size=batch,
        instances_per_device=k,
        snapshot_cap=4 * top,
    )


# Pin each worker to one compute thread (the paper's one-core-per-instance
# shape).  Workers share the checkout's compilation cache
# (repro.compile_cache), so the N-th worker doesn't re-pay the first
# worker's compile.
_WORKER_ENV = {
    "OMP_NUM_THREADS": "1",
    "OPENBLAS_NUM_THREADS": "1",
    "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
}


def _run_fleet(
    n_workers: int,
    k: int,
    total: int,
    chunk: int,
    batch: int,
    scale: int,
    top: int,
    workdir: str,
    env: dict,
):
    src = serve.RMATSource(
        total, chunk_records=chunk, scale=scale, pregenerate=True
    )
    ctl = FleetController(
        _config(k, batch, top),
        n_workers=n_workers,
        workdir=os.path.join(workdir, f"h{n_workers}"),
        report_interval_s=0.5,
        env=env,
    )
    return ctl.run(src)


def main(
    smoke: bool = False,
    hosts_values=(1, 2, 4),
    k: int | None = None,
    total_records: int | None = None,
    chunk: int | None = None,
    batch: int | None = None,
    scale: int | None = None,
):
    k = k if k is not None else (2 if smoke else 4)
    total = total_records if total_records is not None else (
        24_000 if smoke else 400_000
    )
    chunk = chunk if chunk is not None else (1024 if smoke else 4096)
    batch = batch if batch is not None else (256 if smoke else 512)
    scale = scale if scale is not None else (14 if smoke else 18)
    top = int(total * 1.25)
    cores = _usable_cores()
    report = BenchmarkReport("fleet")
    rates: dict[int, float] = {}

    with tempfile.TemporaryDirectory(prefix="bench-fleet-") as workdir:
        env = _WORKER_ENV
        # warmup fleet: populate the shared compilation cache so measured
        # legs time ingest, not XLA compiles
        _run_fleet(1, k, 4 * chunk, chunk, batch, scale, top, workdir, env)
        for hosts in hosts_values:
            rep = _run_fleet(
                hosts, k, total, chunk, batch, scale, top, workdir, env
            )
            if not rep.conserved:
                raise RuntimeError(
                    f"fleet hosts={hosts} lost records: routed "
                    f"{rep.records_in}, delivered {rep.records_delivered}"
                )
            rates[hosts] = rep.aggregate_rate
            params = {
                "hosts": hosts, "k_per_device": k, "total_records": total,
                "batch": batch, "rmat_scale": scale,
            }
            worker_rates = [
                float(w["ingest_rate"] or 0.0) for w in rep.per_worker
            ]
            print(
                f"fleet,aggregate,hosts={hosts},k={k},"
                f"rate={rep.aggregate_rate:,.0f}/s,wall_s={rep.wall_s:.3f},"
                f"conserved={rep.conserved},restarts={rep.restarts}",
                flush=True,
            )
            report.add(
                "fleet_rate", params=params,
                updates_per_sec=rep.aggregate_rate, wall_s=rep.wall_s,
                records_delivered=int(rep.records_delivered),
                conserved=bool(rep.conserved),
                restarts=int(rep.restarts),
                worker_rates=worker_rates,
                **rep.telemetry.serve_counters(),
            )

    # gate the largest fleet the hardware can actually run in parallel;
    # a 1-core box can only attest the N=1 leg (trivially true), but the
    # whole rates-vs-hosts curve still lands in the trend history
    parallelizable = [h for h in hosts_values if h <= cores]
    gate_hosts = max(parallelizable) if parallelizable else min(hosts_values)
    floor_rate = EFFICIENCY_FLOOR * gate_hosts * rates[min(hosts_values)]
    passed = rates[gate_hosts] >= floor_rate
    scaling = rates[gate_hosts] / max(rates[min(hosts_values)], 1e-9)
    print(
        f"verdict,fleet_scaling,{passed},hosts={gate_hosts},"
        f"scaling={scaling:.2f}x,cores={cores},"
        f"floor={EFFICIENCY_FLOOR}*{gate_hosts}",
        flush=True,
    )
    report.add(
        "fleet_scaling",
        params={
            "hosts": gate_hosts, "k_per_device": k,
            "floor": EFFICIENCY_FLOOR, "usable_cores": cores,
            "max_hosts_measured": int(max(hosts_values)),
        },
        passed=bool(passed),
        scaling={str(h): float(r) for h, r in rates.items()},
    )
    report.write()
    return rates


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--hosts", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--total-records", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--scale", type=int, default=None)
    args = ap.parse_args()
    main(
        smoke=args.smoke,
        hosts_values=tuple(args.hosts),
        k=args.k,
        total_records=args.total_records,
        chunk=args.chunk,
        batch=args.batch,
        scale=args.scale,
    )
