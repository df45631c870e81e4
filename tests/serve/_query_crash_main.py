"""Subprocess body for the crash-mid-query chaos test (run by
test_query_plane.py).

Serves a TCP-fed query-plane session (publish_every=1, checkpoint_every=2)
with ``worker.crash_after_n_batches`` armed: the feed loop hard-exits the
process (os._exit, SIGKILL shape — no unwind, no final checkpoint) after
the Nth fed batch while the parent's query client is mid-flight.  Prints
``PORT <n>`` once the listener is up so the parent can connect.

Must run in its own interpreter: os._exit would kill the test process.
"""
import sys

from repro import compile_cache, d4m, serve
from repro.faults import FaultPlan, Trigger

# mirrors the test module's constants — both sides must agree so the
# parent's restored session can load this process's checkpoints
BATCH = 32
CUTS = (8, 32)
CRASH_AFTER_BATCHES = 12


def main():
    compile_cache.enable()
    ckpt_dir = sys.argv[1]
    sess = d4m.D4MStream(
        d4m.StreamConfig(
            cuts=CUTS, top_capacity=4096, batch_size=BATCH,
            instances_per_device=1, snapshot_cap=8192,
        ),
        checkpoint_dir=ckpt_dir,
    )
    plan = FaultPlan().add(
        "worker.crash_after_n_batches", Trigger.once_at(CRASH_AFTER_BATCHES)
    )
    src = serve.TCPSource(port=0, encoding="binary", linger=False)
    server = serve.D4MServer(
        sess, src,
        d4m.ServeConfig(
            max_latency_ms=1e9, checkpoint_every=2, publish_every=1,
            drain_timeout_s=600.0, faults=plan,
        ),
    ).start()
    print(f"PORT {src.port}", flush=True)
    server.join(timeout=600)  # never returns: the fault os._exits first
    print("SURVIVED", flush=True)  # reaching here fails the parent's assert


if __name__ == "__main__":
    main()
