#!/usr/bin/env python
"""Seeded chaos-soak CLI: replay a deterministic fault schedule against
a short coordinated training run and check the standing invariants.

The schedule is a pure function of ``--seed`` — rerunning the same seed
replays the identical event list bit-for-bit (``--schedule-only`` prints
it without training, for quick diffing), which turns any chaos failure
into a reproducible bug report.

Usage::

    python tools/chaos.py --seed 7                  # full soak
    python tools/chaos.py --seed 7 --schedule-only  # just the schedule
    python tools/chaos.py --seed 7 --events 6 --epochs 3 --dir /tmp/run

Output is ONE JSON line with the schedule,
the events that actually fired, the final mesh generation, the
leader-failover count, and the per-invariant verdicts; exit code 0 iff
every invariant held.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seed", type=int, required=True,
                   help="schedule seed (same seed = same events, "
                        "bit-for-bit)")
    p.add_argument("--events", type=int, default=4,
                   help="primary fault events to draw (default 4)")
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--batches", type=int, default=4,
                   help="batches per epoch (default 4)")
    p.add_argument("--dir", default=None,
                   help="run directory (default: a fresh temp dir, "
                        "removed afterwards)")
    p.add_argument("--schedule-only", action="store_true",
                   help="print the seeded schedule and exit (no "
                        "training, no invariants)")
    args = p.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    if args.schedule_only:
        # no training, no devices — the schedule is pure numpy.  The
        # package import still pays for jax (fault/__init__ pulls the
        # supervisor chain), so pin the CPU platform first: the
        # schedule path must never claim an accelerator.
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "jax" in sys.modules:
            import jax
            try:
                jax.config.update("jax_platforms", "cpu")
            except Exception:
                pass
        from deeplearning4j_tpu.fault.chaos import build_schedule
        schedule = build_schedule(args.seed, args.epochs * args.batches,
                                  events=args.events)
        print(json.dumps({"seed": args.seed, "schedule": schedule},
                         sort_keys=True))
        return 0

    # the soak needs 8 virtual host devices, configured before jax loads
    from tools.cpu_proxy import reexec_on_cpu_proxy
    reexec_on_cpu_proxy(8, __file__, sys.argv[1:])
    from deeplearning4j_tpu.fault.chaos import ChaosSoak
    runDir = args.dir or tempfile.mkdtemp(prefix="dl4j_chaos_")
    cleanup = args.dir is None
    try:
        report = ChaosSoak(args.seed, runDir, epochs=args.epochs,
                           batchesPerEpoch=args.batches,
                           events=args.events).run()
    finally:
        if cleanup:
            shutil.rmtree(runDir, ignore_errors=True)
    print(json.dumps(report, sort_keys=True, default=str))
    return 0 if report.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
