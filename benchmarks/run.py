"""Benchmark orchestrator — config-driven experiments over paper sections.

The canonical entry point is an experiment config::

    python -m benchmarks.run --experiment benchmarks/experiments/ci-smoke.json

which loads a :class:`repro.bench.ExperimentSpec` (sections × engine × K ×
D × source from one JSON/TOML file; ``matrix`` axes cross-multiply into
legs) and executes every leg, writing ``BENCH_<section>.json`` per section
(schema: ``repro.bench.reporting``) plus ``name,...`` CSV lines.  Sections:
  hier            — paper Figs. 4/5 (update rate vs cuts, instantaneous decay)
  scaling         — paper Fig. 6 shape: aggregate rate vs instances, on two
                    axes — D devices (run standalone or with
                    XLA_FLAGS=--xla_force_host_platform_device_count=8) and
                    K vmap-packed instances per device (K ∈ {1, 8, 64, 256})
  kernels         — Pallas kernel ref/interp microbenches + TPU design stats
  embed           — LM integration: hierarchical sparse embedding-grad traffic
  cascade_kernel  — lane-skipping hier_cascade kernel vs the branchless
                    cascade: per-step cost vs cascade frequency x K
  serve           — streaming ingress loop (repro.serve): sustained served
                    rate vs raw-engine rate at K ∈ {1, 8}, with the
                    feed_efficiency (>= 50% at K=8) verdict + a loopback
                    TCP socket leg
  fleet           — multi-process scale-out (repro.fleet): aggregate served
                    rate vs worker count (hosts × K sweep over subprocess
                    workers behind the two-level hash router), with the
                    fleet_scaling (>= 0.7 × min(N, cores) × single-worker
                    rate) verdict and record-conservation checks

The legacy flags (``--section hier``, ``--sections hier,scaling``,
``--smoke``, ``--full``) still work as a deprecation shim: they synthesize
the equivalent spec via ``ExperimentSpec.from_legacy`` with the exact
historical parameter values, so archived rate trajectories stay comparable.
Prefer a committed config file for anything you run twice.
"""
import argparse
import os
import sys

from repro import compile_cache
from repro.bench.experiments import (  # noqa: F401  (SECTIONS re-exported)
    SECTIONS,
    ExperimentError,
    ExperimentSpec,
    run_spec,
)


def parse_sections(args: argparse.Namespace) -> set:
    """Legacy section selection (kept for callers importing this helper)."""
    if args.sections:
        chosen = {s.strip() for s in args.sections.split(",") if s.strip()}
        bad = chosen - set(SECTIONS)
        if bad:
            raise SystemExit(
                f"unknown section(s) {sorted(bad)}; known: {list(SECTIONS)}"
            )
        return chosen
    if args.section == "all":
        return set(SECTIONS)
    return {args.section}


def build_spec(args: argparse.Namespace) -> ExperimentSpec:
    if args.experiment:
        if args.sections or args.section != "all" or args.smoke or args.full:
            raise SystemExit(
                "--experiment replaces --section/--sections/--smoke/--full; "
                "put the legs in the config file instead"
            )
        return ExperimentSpec.from_file(args.experiment)
    if args.sections or args.section != "all" or args.smoke or args.full:
        print(
            "run,deprecated,--section/--sections/--smoke/--full are legacy; "
            "use --experiment <config.json> (see benchmarks/experiments/)",
            file=sys.stderr,
        )
    # stable leg order: the canonical SECTIONS order, not the set's
    chosen = parse_sections(args)
    ordered = [s for s in SECTIONS if s in chosen]
    return ExperimentSpec.from_legacy(
        ordered, smoke=args.smoke, full=args.full, json_dir=args.json_dir
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--experiment", default=None, metavar="CONFIG",
                    help="experiment config (JSON or TOML) defining the legs "
                         "to run; replaces the legacy section flags")
    ap.add_argument("--section", default="all",
                    choices=["all", *SECTIONS])
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of sections to run "
                         f"(overrides --section): {','.join(SECTIONS)}")
    ap.add_argument("--full", action="store_true", help="paper-scale streams")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-size streams (fast, still exercises every path)")
    ap.add_argument("--json-dir", default=None,
                    help="directory for BENCH_<section>.json (default: cwd)")
    args = ap.parse_args()
    compile_cache.enable()
    if args.json_dir:
        os.environ["BENCH_JSON_DIR"] = args.json_dir
    try:
        spec = build_spec(args)
    except ExperimentError as e:
        raise SystemExit(str(e))
    run_spec(spec, json_dir=args.json_dir)


if __name__ == "__main__":
    main()
