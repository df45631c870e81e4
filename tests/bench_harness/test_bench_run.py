"""The harness end to end on the CPU: it refuses to run without a TPU, and a
run whose timed path is broken comes out not correct.

The runs here skip the harness's look for a chip and drive the rest of a
run (generator process, warm-up, measured serve, drain, comparison) at a
size a test can hold; the chip runs are the benchmark's own."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

import run
from repro.core.assoc import PAD

ROOT = Path(__file__).resolve().parents[2]
TINY = dict(cuts=[256, 2048], top_capacity=16384, batch_size=256,
            warmup_batches=4, prefill={"records_per_instance": [0, 1500, 6000]})


@pytest.mark.parametrize("workload", ["k32-rmat-ingest", "single-rmat-ingest"])
def test_no_tpu_no_result(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed", str(2**31 + 9),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def _spec(engine, k):
    spec = run.resolve("k32-rmat-ingest")
    # one instance takes the records of four: give it a top that holds them
    top = TINY["top_capacity"] * (64 if k == 1 else 1)
    spec["config"] = dict(spec["config"], engine=engine, instances_per_device=k,
                          **dict(TINY, top_capacity=top))
    # paced, so the records of a one-second window fit the tiny layers
    spec["traffic"] = dict(spec["traffic"], rate=16384)
    return spec


def _half(rows, cols, vals):
    keep = jnp.arange(rows.shape[-1]) % 2 == 0  # every other slot of every lane
    return jnp.where(keep, rows, PAD), jnp.where(keep, cols, PAD), jnp.where(keep, vals, 0)


FAULTS = {
    # a step that returns its state unchanged
    "unchanged": lambda step: (lambda h, r, c, v: h),
    # half of every batch left out
    "half_batch": lambda step: (lambda h, r, c, v: step(h, *_half(r, c, v))),
    # every value altered where it is produced
    "altered": lambda step: (lambda h, r, c, v: step(h, r, c, v * (1 + 2.0**-10))),
}
FAILS = {"unchanged": "keys_wrong", "half_batch": "keys_wrong", "altered": "value_rel_err"}


@pytest.mark.parametrize("engine,k", [("single", 1), ("packed", 4)])
def test_sound_run_is_correct(engine, k):
    res = run.run_cell(_spec(engine, k), seed=2**31 + 101, seconds=1.0, trace=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] > TINY["warmup_batches"] * TINY["batch_size"]
    assert res["metrics"]["update_rate"]["value"] > 0
    assert list(res)[-1] == "checks"
    json.dumps(res)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault):
    def hook(sess):
        sess._step = FAULTS[fault](sess._step)

    res = run.run_cell(_spec("packed", 4), seed=2**31 + 202, seconds=1.0, trace=False,
                       session_hook=hook)
    assert not res["correct"]
    check = res["checks"][FAILS[fault]]
    assert check["value"] > check["limit"]
    assert res["checks"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("batch,want", [(512, 4096), (256, 4096), (3000, 3000), (100_000, 4000)])
def test_generator_chunks_tile_whole_microbatches(batch, want):
    chunk = run.chunk_for(batch, 4096)
    assert chunk == want
    assert chunk % batch == 0 or batch % chunk == 0


def test_traced_run_fails_when_a_metric_finds_nothing():
    """On the CPU the trace holds no TPU plane, so the device metrics have
    nothing to read: the traced run fails instead of leaving them out."""
    with pytest.raises(run.MetricMissing, match="found nothing"):
        run.run_cell(_spec("packed", 4), seed=2**31 + 303, seconds=1.0, trace=True)
