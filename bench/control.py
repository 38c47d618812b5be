#!/usr/bin/env python3
"""Readings that the check's limits are set from, for one cell, in one
process on the chip.

    python3 bench/control.py --workload <cell> --seeds 12 --control 3 \\
        --first <seed> --seconds <s>

Set-up is made once.  Then, for each of ``--seeds`` seeds (``--first``,
``--first + 7919``, ...), it draws that seed's traffic, runs a window of
``--seconds`` at the cell's load, and counts the check's numbers for the
program (the lower readings).  For the first ``--control`` of them it also
counts them with the control in the program's place: the reference in
float32 (``bench/reference.py``), judged against the int32 reference (the
upper readings).  One JSON line per seed, then a summary line: the largest
program reading and the smallest control reading of each number, and
whether the control failed the check on every seed it ran.  The
benchmark's own runs never run this.  Exits 2 without a TPU.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime's logs would go to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import check, harness, spec
    from bench.run import chips

    cell = spec.load_cell(args.workload)
    if chips(cell.chips) is None:
        return 2
    import jax

    from repro.kernels import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kernels = harness.map_kernels(cell)
    seeds = [args.first + 7919 * i for i in range(args.seeds)]
    harness.start_traffic(kernels, seeds[0])
    harness.warm_up(kernels, cell)
    lower = dict.fromkeys(check.LIMITS, 0)
    upper = dict.fromkeys(check.LIMITS)
    control_failed = True
    for i, seed in enumerate(seeds):
        harness.start_traffic(kernels, seed)
        jobs = harness.measure(kernels, cell, args.seconds, False).jobs
        line = {"seed": seed, "jobs": len(jobs),
                "program": harness.compare(kernels, cell, jobs)}
        if i < args.control:
            line["control"] = harness.compare(kernels, cell, jobs, "float32")
            control_failed &= not check.correct(line["control"])
            for k, v in line["control"].items():
                upper[k] = v if upper[k] is None else min(upper[k], v)
        for k, v in line["program"].items():
            lower[k] = max(lower[k], v)
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": cell.name, "seeds": len(seeds),
                      "lower": lower, "upper": upper,
                      "control_failed_every_seed": control_failed,
                      "limits": check.LIMITS,
                      "seconds": time.monotonic() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
