#!/usr/bin/env python3
"""The on-chip benchmark of the batched verify path: one cell per process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits 2, printing no result, unless JAX's first device is a TPU and there
are as many devices as the cell asks for.  Otherwise it runs the cell
(``bench/harness.py``) and prints, on standard output, one line of compile
counts for set-up and for the window and, last, the result line: one JSON
object with ``correct``, ``attempted`` and ``failed`` (memories),
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and
``checks``, each compared number with its limit.  Those numbers are also
the last lines of standard error.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips(count: int):
    """The device line, or None when the chips the cell asks for are not
    there."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < count:
        print(f"bench: needs {count} TPU chip(s), JAX found {len(devices)} "
              f"{platform!r} device(s); nothing was run", file=sys.stderr)
        return None
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime's logs would go to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import spec

    cell = spec.load_cell(args.workload)
    import jax

    t_jax = time.monotonic()
    device = chips(cell.chips)
    if device is None:
        return 2
    t_runtime = time.monotonic()
    import numpy as np

    from bench.compiles import CompileCounter
    from bench.harness import run_cell
    from repro.kernels import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    jax.device_put(np.zeros(1, np.int32)).block_until_ready()
    print(f"bench: start: imports {t_jax - T_START:.2f} s, TPU runtime "
          f"{t_runtime - t_jax:.2f} s, harness imports and first transfer "
          f"{time.monotonic() - t_runtime:.2f} s", file=sys.stderr, flush=True)
    outcome = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       T_START, device, counter)
    print(json.dumps({"compiles": outcome.compiles}), flush=True)
    print("\n".join(outcome.checks), file=sys.stderr, flush=True)
    print(json.dumps(outcome.result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
