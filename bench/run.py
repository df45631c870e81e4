#!/usr/bin/env python3
"""Run one cell of the served D4M store's benchmark, once, on this machine's chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``:
the deployment's sizes, engine and serve settings) and a traffic mix
(``bench/traffic/<name>.json``); every metric is read by its own reader,
``bench/metrics/<name>.py``.  The run:

1. fails, printing no result, unless JAX finds a TPU with the chips the cell
   asks for (it never falls back to the CPU);
2. starts the load generator (``bench/loadgen.py``, a process that never
   imports JAX), builds the session with the configuration's engine pinned,
   fills its layers to the configuration's ``prefill`` (``bench/prefill.py``;
   the reference starts from the same entries), and warms
   up by serving a fixed prefix of the stream through a ``TCPSource`` into a
   ``D4MServer`` and draining it, which compiles every program the window
   runs and syncs the device;
3. starts the measured ``D4MServer`` on a fresh ``TCPSource`` (binary
   ``D4MF`` wire); the window starts when ``start()`` returns.  The
   generator sends the stream on for ``--seconds``, then closes its
   connection, and the server drains, ending in a device sync;
4. counts the programs compiled or loaded inside the window, reads the
   device's memory peak, reads the drained state back and compares it, and
   the conservation of records, with the plain reference
   (``bench/reference.py``);
5. prints the numbers compared beside their limits on stderr, and as its
   last stdout line one JSON object with ``correct``, ``attempted``,
   ``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced) and
   the ``checks`` last.

``--trace 1`` is a run of its own: it turns the program's ``obs`` metrics
on, samples the router queue every 100 ms, profiles the whole window, drain
included, with ``jax.profiler`` and reports the cell's per-layer metrics
instead of its end-to-end ones.  A per-layer metric of the cell that finds
nothing to read fails the run.  JAX's persistent compilation cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
OUT = ROOT / ".bench_out"  # traces; listed in .gitignore
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import prefill  # noqa: E402
import reference  # noqa: E402
import stream  # noqa: E402
import devtrace  # noqa: E402
import peaks  # noqa: E402

SAMPLE_S = 0.1  # router queue sampling period in traced runs
DRAIN_TIMEOUT_S = 240.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class MetricMissing(RuntimeError):
    """A metric the cell reports found nothing to read."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the manifest and the files it names
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: Path = ROOT) -> Dict[str, Any]:
    """The cell ``workload`` of ``BENCHMARK.json`` with its configuration,
    traffic mix and the names of the metrics it reports."""
    man = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in man["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in man["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")

    def reported(metrics):
        return [m["name"] for m in metrics if workload in m.get("workloads", [workload])]

    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": reported(man["end_to_end"]),
        "per_layer": reported(man["per_layer"]),
        "units": {m["name"]: m["unit"] for m in man["end_to_end"] + man["per_layer"]},
    }


def reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chip(chips: int):
    """The TPU devices, or :class:`NoChip`."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found {len(devs)}")
    peaks.lookup(devs[0].device_kind)  # an unknown chip is an error
    return devs


# ---------------------------------------------------------------------------
# pieces of a run
# ---------------------------------------------------------------------------

class CompileCount:
    """Programs compiled or loaded from the persistent cache, from JAX's own
    monitoring events (one per backend compile request)."""

    _instance = None

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    @classmethod
    def get(cls) -> "CompileCount":
        if cls._instance is None:  # listeners cannot be removed: register once
            cls._instance = CompileCount()
        return cls._instance


class Generator:
    """The load generator process and its line protocol (see loadgen.py)."""

    def __init__(self, job: Dict[str, Any]):
        env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        self.proc = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py"), json.dumps(job)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, bufsize=1,
            cwd=str(ROOT), env=env,
        )
        self._lines: "queue.Queue[Optional[str]]" = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        self._lock = threading.Lock()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def send(self, **cmd) -> None:
        with self._lock:
            self.proc.stdin.write(json.dumps(cmd) + "\n")
            self.proc.stdin.flush()

    def recv(self, timeout: float) -> Dict[str, Any]:
        line = self._lines.get(timeout=timeout)
        if line is None:
            raise RuntimeError(f"load generator exited with {self.proc.wait()}")
        msg = json.loads(line)
        if msg.get("op") == "error":
            raise RuntimeError(f"load generator: {msg['error']}")
        return msg

    def close(self) -> None:
        try:
            if self.proc.poll() is None:
                self.send(op="exit")
                self.proc.stdin.close()
                self.proc.wait(timeout=30)
        except (OSError, ValueError, subprocess.TimeoutExpired):
            self.proc.kill()
            self.proc.wait()


class GCPauses:
    """The interpreter's garbage collections during the window (they stop
    every thread of the server), reported beside the results."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class QueueSampler(threading.Thread):
    """Samples the router's routed-batch queue every 100 ms."""

    def __init__(self, server):
        super().__init__(daemon=True)
        self.server = server
        self.samples = []
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(SAMPLE_S):
            self.samples.append(self.server.router.counters()["queue_depth"])

    def stop(self) -> None:
        self._halt.set()
        self.join()


def stream_config(config: Dict[str, Any]):
    from repro import d4m

    return d4m.StreamConfig(
        cuts=tuple(config["cuts"]),
        top_capacity=int(config["top_capacity"]),
        batch_size=int(config["batch_size"]),
        instances_per_device=int(config["instances_per_device"]),
        engine=config["engine"],
        semiring=config["semiring"],
        dtype=config["dtype"],
    )


def chunk_for(batch: int, target: int) -> int:
    """The generator's chunk size nearest ``target`` that tiles whole
    microbatches: a multiple of ``batch``, or a divisor of it.  Whole
    batches keep the router from holding a partial batch between chunks,
    which a stale flush would send short."""
    if batch <= target:
        return target // batch * batch
    k = -(-batch // target)
    while batch % k:
        k += 1
    return batch // k


def read_state(sess):
    """The drained state on the host: ``(rows, cols, vals)`` of every live
    slot of every lane of every layer, and whether any layer overflowed."""
    import jax

    host = jax.device_get(sess.state)
    parts, overflowed = [], False
    for layer in host.layers:
        nnz = np.atleast_1d(np.asarray(layer.nnz))
        lanes = nnz.size
        rows = np.asarray(layer.rows).reshape(lanes, -1)
        cols = np.asarray(layer.cols).reshape(lanes, -1)
        vals = np.asarray(layer.vals).reshape(lanes, -1)
        for k in range(lanes):
            n = int(nnz[k])
            parts.append((rows[k, :n], cols[k, :n], vals[k, :n]))
        overflowed |= bool(np.asarray(layer.overflow).any())
    return parts, overflowed


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(
    spec: Dict[str, Any],
    seed: int,
    seconds: float,
    trace: bool,
    control: bool = False,
    session_hook: Optional[Callable[[Any], None]] = None,
) -> Dict[str, Any]:
    """Run the cell ``spec`` (see :func:`resolve`) once; returns the result
    line's object.  ``session_hook(sess)`` runs on the built session before
    the warm-up (the fault tests break the timed path with it).  With
    ``control``, the bfloat16 reference is also compared in the program's
    place and its numbers logged."""
    import jax

    from repro import compile_cache, d4m, serve

    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    phases = {"jax_and_chip": time.monotonic() - T_PROCESS}
    compile_cache.enable()
    compiles = CompileCount.get()
    batch = int(config["batch_size"])
    chunk = chunk_for(batch, int(traffic["chunk_records"]))
    warm_records = int(config["warmup_batches"]) * batch
    warm_records = -(-warm_records // chunk) * chunk
    gen = Generator({"seed": int(seed), "traffic": traffic, "chunk_records": chunk})
    try:
        mark = time.monotonic()
        gen.recv(timeout=120)  # ready
        phases["generator"] = time.monotonic() - mark
        mark = time.monotonic()
        sess = d4m.D4MStream(stream_config(config))
        jax.block_until_ready(sess.state)
        phases["session"] = time.monotonic() - mark

        # the state at a deployment's fill, and the reference's copy of it
        mark = time.monotonic()
        base_parts, counts = prefill.fill(sess, config["prefill"], traffic["rmat"], seed,
                                          fill_limits(config))
        jax.block_until_ready(sess.state)
        phases["prefill"] = time.monotonic() - mark
        log("prefill: live keys per instance by built layer (min, max): "
            + json.dumps([[int(c.min()), int(c.max())] for c in counts]))
        if session_hook is not None:
            session_hook(sess)
        mark, compile_s = time.monotonic(), compiles.seconds
        serve_kw = dict(config.get("serve", {}), drain_timeout_s=DRAIN_TIMEOUT_S)

        # warm-up: the same socket path, a fixed prefix, drained
        src = serve.TCPSource(port=0, encoding="binary", linger=False)
        warm = serve.D4MServer(sess, src, d4m.ServeConfig(metrics=False, **serve_kw)).start()
        gen.send(op="warmup", port=src.port, records=warm_records)
        gen.recv(timeout=600)
        if not warm.join(timeout=DRAIN_TIMEOUT_S):
            raise RuntimeError("the warm-up serve did not drain")
        warm_fed = warm.records_fed
        if warm_fed != warm_records:
            raise RuntimeError(f"warm-up fed {warm_fed} of {warm_records} records")
        del warm
        phases["warmup"] = time.monotonic() - mark
        phases["warmup_compile"] = compiles.seconds - compile_s
        mark = time.monotonic()

        # the measured serve
        src = serve.TCPSource(port=0, encoding="binary", linger=False)
        server = serve.D4MServer(sess, src, d4m.ServeConfig(metrics=bool(trace), **serve_kw))
        queue_depth = server.config.queue_depth
        gc_pauses = GCPauses()
        sampler = QueueSampler(server) if trace else None
        profiler = Profiler(cell["name"]) if trace else None
        if profiler is not None:
            profiler.start()
        with gc_pauses:
            server.start()
            t0 = time.monotonic()
            phases["serve_start"] = t0 - mark
            c0 = compiles.n
            gen.send(op="window", port=src.port, seconds=float(seconds))
            if sampler is not None:
                sampler.start()
            done = gen.recv(timeout=float(seconds) + 120)
            t_sent = time.monotonic()
            if not server.join(timeout=DRAIN_TIMEOUT_S):
                raise RuntimeError("the measured serve did not drain")
            t1 = time.monotonic()
            c1 = compiles.n
        traced = profiler.stop() if profiler is not None else None
        if sampler is not None:
            sampler.stop()
        tel = server.telemetry()
        spans = server.trace.events() if server.trace is not None else []
        dev = jax.devices()[0]
        peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
        state_parts, overflowed = read_state(sess)
        del server, sess
        gc.collect()
    finally:
        gen.close()

    # the reference, once the window has closed and the state is on the host
    window_records = int(tel.records_fed)
    sent = warm_records + int(done["records"])
    fed = warm_fed + window_records
    rows, cols, vals = stream.Stream(seed, traffic["rmat"], chunk).prefix(fed)
    want = reference.dok_from(base_parts, rows, cols, vals)
    got = reference.state_dok(state_parts)
    numbers = reference.compare_state(got, want)
    numbers["records_lost"] = (sent - fed) + int(tel.records_dropped) + int(tel.malformed)
    numbers["overflowed"] = int(overflowed)
    numbers["window_compiles"] = c1 - c0
    numbers["window_empty"] = int(window_records == 0)
    correct, checks = reference.judge(numbers)
    if control:
        ctl = reference.compare_state(reference.dok_bfloat16(base_parts, rows, cols, vals), want)
        log("control (bfloat16 reference in the program's place): " + json.dumps(ctl))

    window_s = t1 - t0
    log(f"device memory peak {peak} bytes; live keys at the window's end {got[0].size}")
    log("set-up by phase (s): " + json.dumps({k: round(v, 3) for k, v in phases.items()}))
    per = np.bincount((np.asarray(done["stamps_s"]) // 5).astype(int)) * chunk
    log(f"generator closed at {t_sent - t0:.3f} s, drain ended at {t1 - t0:.3f} s; "
        f"records sent per 5 s: {per.tolist()}")
    gens = [0, 0, 0]
    for g, _ in gc_pauses.pauses:
        gens[g] += 1
    log(f"garbage collections in the window by generation {gens}, longest "
        f"{max((d for _, d in gc_pauses.pauses), default=0.0):.4f} s")
    log(f"window {window_s:.3f} s, {window_records} records applied, generator sent "
        f"{done['records']} in {done['chunks']} chunks, at most {done['late_s']:.3f} s "
        f"behind its schedule, in {done['seconds']:.3f} s")
    ctx = {
        "setup_s": t0 - T_PROCESS,
        "window_s": window_s,
        "window_records": window_records,
        "config": config,
        "spans": spans,
        "queue_samples": sampler.samples if sampler is not None else [],
        "queue_depth": queue_depth,
        "trace": traced,
    }
    names = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for name in names:
        value = reader(name)(ctx)
        if value is None:
            raise MetricMissing(f"metric {name} found nothing to read in this run")
        metrics[name] = {"value": value, "unit": spec["units"][name]}
    result = {
        "correct": correct,
        "attempted": sent,
        "failed": int(numbers["records_lost"]),
        "metrics": metrics,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak,
        },
    }
    if traced is not None:
        result["device"]["busy_s"] = traced["busy_s"]
        result["device"]["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"], "idle_gaps": traced["idle_gaps"]}
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    return result


def fill_limits(config: Dict[str, Any]):
    """The most live keys each layer of an instance may hold after the
    prefill: below its cut (or it would cascade at once), and the top below
    its capacity."""
    return [int(c) for c in config["cuts"]] + [int(config["top_capacity"])]


class Profiler:
    """``jax.profiler`` around the whole window, drain included: every step
    the window runs lies wholly inside the trace."""

    def __init__(self, name: str):
        self.out = OUT / "trace" / name
        self._span = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python-call tracing would slow every host thread
        jax.profiler.start_trace(str(self.out), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> Optional[Dict[str, Any]]:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        files = sorted(self.out.glob("**/*.xplane.pb"))
        if not files:
            return None
        return devtrace.reduce(devtrace.load_events(str(files[-1])))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also compare the bfloat16 reference in the program's place")
    ap.add_argument("--dtype", default=None,
                    help="run the program at this value dtype instead of the "
                         "configuration's (the lower-precision control)")
    args = ap.parse_args(argv)
    spec = resolve(args.workload)
    if args.dtype is not None:
        spec["config"] = dict(spec["config"], dtype=args.dtype)
    try:
        require_chip(int(spec["cell"]["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import jax

    # every program, however quick to compile, comes from the cache after
    # the first run, so set-up does the same work in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace), bool(args.control))
    except MetricMissing as e:
        print(f"bench: {e}", file=sys.stderr)
        return 4
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
