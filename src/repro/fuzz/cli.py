"""``python -m repro fuzz`` — the batched differential fuzzing fleet.

Examples::

    repro fuzz --kernels bitcount,dotprod --memories 1024
    repro fuzz --arch 4x4,mesh-4x4,bordermem-4x4 --memories 10000 --shrink
    repro fuzz --kernels all --json --out results/fuzz.json
    repro fuzz --kernels gsm --memories 16384 --trace fuzz-trace
    repro trace report fuzz-trace     # where the fuzz run spent its time

Each (kernel, arch) pair is mapped through a
:class:`~repro.toolchain.session.Toolchain` (content-addressed cache
supported via ``--cache-dir``), fuzzed over a deterministic seeded corpus
in batched PE-array dispatches, and differentially checked against the
vectorized reference oracle.  ``--shrink`` turns mismatches into
single-memory reproducer JSONs under ``--failures-dir``.  The JSON
digest (``--json`` / ``--out``) is the artifact the CI fuzz lanes gate
with ``benchmarks/check_regression.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from .corpus import STRATEGIES
from .engine import FuzzReport, fuzz_kernel


def _resolve_kernels(spec: str) -> List[str]:
    from ..cgra.registry import kernel_names

    if spec == "all":
        return kernel_names()
    names = [k.strip() for k in spec.split(",") if k.strip()]
    known = set(kernel_names(variants=True))
    unknown = [k for k in names if k not in known]
    if unknown:
        raise SystemExit(f"unknown kernel(s): {', '.join(unknown)} "
                         f"(see: repro list)")
    return names


def _print_human(rep: FuzzReport) -> None:
    head = f"{rep.kernel} @ {rep.arch}"
    if rep.status in ("unmapped", "timeout", "error"):
        why = f" — {rep.error}" if rep.error else ""
        print(f"{head}: {rep.status}{why}")
        return
    verdict = "ok" if rep.ok else f"MISMATCH ({len(rep.failing)} memories)"
    print(f"{head}: {verdict}  II={rep.ii}  {rep.memories} memories "
          f"of {rep.mem_words} words @ {rep.mem_rate:.0f} mem/s "
          f"(batch {rep.batch})")
    if rep.energy:
        e = rep.energy
        print(f"  dynamic energy: static {e['static_dynamic_nj']} nJ -> "
              f"empirical {e['empirical_dynamic_nj']} nJ "
              f"({e['delta_pct']:+.1f}%)")
    for line in rep.mismatches[:4]:
        print(f"  {line}")
    if rep.divergence:
        d = rep.divergence
        print(f"  first divergence: cycle {d['cycle']}, PE {d['pe']}, "
              f"node {d['node']} (iteration {d['iteration']})")
    if rep.reproducer:
        print(f"  reproducer: {rep.reproducer}")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro fuzz",
        description="batched differential fuzzing of mapped kernels",
    )
    ap.add_argument("--kernels", default="all",
                    help="comma-separated registry kernels, or 'all' "
                         "(default)")
    ap.add_argument("--arch", default="4x4",
                    help="comma-separated architecture specs/presets "
                         "(default 4x4)")
    ap.add_argument("--memories", type=int, default=1024,
                    help="corpus size per (kernel, arch) (default 1024)")
    ap.add_argument("--batch", type=int, default=1024,
                    help="memories per PE-array dispatch (default 1024)")
    ap.add_argument("--seed", type=int, default=0,
                    help="corpus base seed (default 0)")
    ap.add_argument("--strategies", default=None,
                    help=f"comma-separated corpus strategies "
                         f"(default: all of {','.join(STRATEGIES)})")
    ap.add_argument("--shrink", action="store_true",
                    help="on mismatch: bisect to one memory, replay the "
                         "divergence, write a reproducer JSON")
    ap.add_argument("--failures-dir", default="results/fuzz_failures",
                    help="where --shrink writes reproducers")
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="total mapping budget per kernel in seconds "
                         "(default 120)")
    ap.add_argument("--ii-max", type=int, default=32)
    ap.add_argument("--cache-dir", default=None,
                    help="content-addressed mapping cache")
    ap.add_argument("--json", action="store_true",
                    help="print the JSON digest instead of a summary")
    ap.add_argument("--out", default=None, help="also write the digest here")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="record an obs trace of the run into DIR; "
                         "`repro trace report DIR` then shows where the "
                         "fuzz run spent its time (decode, dispatch, "
                         "device wait, transfer, oracle, harvest)")
    ap.add_argument("--strict", action="store_true",
                    help="also exit non-zero on unmapped/timed-out "
                         "kernels (default: only mismatches and engine "
                         "errors fail the fleet — a kernel that blows "
                         "its mapping budget is a loudly-reported "
                         "coverage gap, not a correctness verdict)")
    args = ap.parse_args(argv)
    if args.trace:
        from ..obs import trace as obs_trace

        obs_trace.enable(args.trace)

    from ..cgra.registry import ensure_registered
    from ..core.mapper import MapperConfig

    ensure_registered()
    kernels = _resolve_kernels(args.kernels)
    archs = [a.strip() for a in args.arch.split(",") if a.strip()]
    cfg = MapperConfig(per_ii_timeout_s=args.timeout / 2,
                       total_timeout_s=args.timeout, ii_max=args.ii_max)
    strategies = (tuple(s.strip() for s in args.strategies.split(","))
                  if args.strategies else None)

    reports: List[FuzzReport] = []
    for arch in archs:
        for name in kernels:
            rep = fuzz_kernel(
                name, arch=arch, memories=args.memories, batch=args.batch,
                seed=args.seed, shrink=args.shrink,
                config=cfg, cache=args.cache_dir,
                failures_dir=args.failures_dir, strategies=strategies)
            reports.append(rep)
            if not args.json:
                _print_human(rep)

    doc = {
        "bench": "fuzz",
        "archs": archs,
        "kernels": kernels,
        "memories": args.memories,
        "batch": args.batch,
        "seed": args.seed,
        "results": [r.to_dict() for r in reports],
        "mismatches": sum(1 for r in reports if r.status == "mismatch"),
        "errors": sum(1 for r in reports if r.status == "error"),
        "unmapped": sum(1 for r in reports
                        if r.status in ("unmapped", "timeout")),
    }
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
    if args.json:
        print(json.dumps(doc, indent=1, sort_keys=True))
    if doc["unmapped"] and not args.json:
        gaps = [f"{r.kernel}@{r.arch}" for r in reports
                if r.status in ("unmapped", "timeout")]
        print(f"NOTE coverage gaps (not fuzzed, mapping budget): "
              f"{', '.join(gaps)}")
    bad = doc["mismatches"] + doc["errors"]
    if args.strict:
        bad += doc["unmapped"]
    if bad and not args.json:
        print(f"{bad}/{len(reports)} (kernel, arch) pairs failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
