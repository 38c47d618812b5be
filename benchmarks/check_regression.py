"""CI benchmark-regression gate: compare a fresh BENCH JSON to a baseline.

  python benchmarks/check_regression.py CURRENT BASELINE [--time-tol 0.25]

Three artifact shapes are understood:

* ``benchmarks/incremental_solver.py`` row lists — rows are joined on
  (cil, size, backend);
* ``benchmarks/portfolio.py`` row lists (``bench: "portfolio"``) — rows
  are joined on (cil, size, strategy); committed II, II-equality with
  the sequential ladder and the summary's ``all_same_ii`` flag are hard
  (the racer's determinism contract), the three wall-time columns are
  tolerance-gated;
* ``repro.dse`` sweep documents — points are joined on (kernel, size)
  and the whole Pareto section must match exactly;
* ``benchmarks/arch_dse.py`` documents (``bench: "arch_dse"``) — points
  are joined on (kernel, arch); Pareto + the acceptance block must match;
* ``python -m repro map --json`` digests (``bench: "toolchain_map"``) —
  the single-kernel toolchain smoke (heterogeneous specs carry an
  ``arch`` field that is gated too);
* ``benchmarks/serving.py`` documents (``bench: "serving"``) — points
  are joined on (kernel, arch); per-point status/II/mII and the dedup
  contract (compiles == unique points, duplicate results identical,
  deterministic cache-hit ratio) are hard, throughput/latency
  percentiles are tolerance-gated;
* ``python -m repro fuzz --out`` documents (``bench: "fuzz"``) —
  results are joined on (kernel, arch); status/II/failing indices,
  the activity-based energy delta and the first-divergence record are
  hard (the whole pipeline is seeded and bit-exact), memories/sec is
  tolerance-gated;
* ``benchmarks/fuzz_throughput.py`` documents
  (``bench: "fuzz_throughput"``) — rows are joined on kernel; the
  sequential-vs-batched verdict agreement is hard, all three rates and
  the derived speedups are tolerance-gated;
* ``benchmarks/obs_overhead.py`` documents (``bench: "obs"``) — cases
  are joined on (kernel, arch); status/II, the tracing-does-not-perturb
  flag (``same_ii``), span counts, trace validity and the attribution
  and disabled-overhead verdicts are hard (all machine-independent
  booleans); the off/on wall clocks are tolerance-gated and the raw
  overhead percentages are reported only.

``--assert-identical`` additionally serializes the *correctness
projection* of both sides (every machine-independent field, canonical
key order) and requires the bytes to be equal — the strongest form of
the smoke-baseline contract: not just joined fields but the full row
sets must survive a refactor byte-for-byte.

Correctness fields (status, II, Pareto fronts, cross-check flags) must be
identical — any drift hard-fails.  Wall-time fields are compared with a
relative tolerance (default ±25%); points where both sides are faster
than ``--time-floor`` seconds are skipped, since sub-second timings are
noise-dominated on shared CI runners.

``--correctness-only`` disables the wall-time comparison entirely: the
PR-path CI lane gates only machine-independent fields (II, feasibility,
Pareto membership, cache determinism) so shared-runner jitter cannot flake
a pull request; wall-time gating lives in the nightly workflow, whose
runners are at least consistently loaded across a night's runs.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Tuple

INC_HARD = ("status", "ii", "same_result", "all_same_result")
INC_TIME = ("cold_s", "incremental_s")
# geomean speedups are wall-time-derived, so only the determinism flags
# and the committed IIs are hard for the portfolio lane
PORT_HARD = ("status", "ii", "ii_sequential", "same_ii", "all_same_ii")
PORT_TIME = ("cold_s", "incremental_s", "portfolio_s")
DSE_HARD = ("status", "ii", "utilization", "latency_cycles", "energy_nj",
            "cegar_rounds")
DSE_TIME = ("map_time_s",)
ARCHDSE_HARD = ("status", "ii", "mii", "utilization", "latency_cycles",
                "energy_nj", "area", "validated", "assemblable",
                "topology", "num_pes")
ARCHDSE_TIME = ("map_time_s",)
TOOLMAP_HARD = ("bench", "kernel", "grid", "arch", "status", "stage", "ii",
                "mii", "backend", "map_status", "cegar_rounds", "oracle",
                "utilization", "metrics", "error")
TOOLMAP_TIME = ("wall_time_s",)
# the cache/coalesced split depends on arrival timing, so only the
# deterministic dedup contract (compiles == unique points, duplicates
# byte-identical, hit ratio = duplicates/n) is hard for the serving lane
SERVING_HARD = ("requests", "status", "stage", "error", "ii", "mii",
                "map_status", "backend", "utilization")
SERVING_TOP_HARD = ("mode", "seed", "zipf_s", "arches", "kernels",
                    "kernel_arches", "kernel_config", "backend",
                    "n_requests", "unique_points", "compiles",
                    "duplicates", "identical_duplicates", "dedup_ok",
                    "cache_hit_ratio", "rejected", "errors")
SERVING_TIME = ("throughput_rps", "p50_ms", "p99_ms", "wall_time_s")
# fuzz verdicts are deterministic end to end (seeded corpus, fixed
# mapping, bit-exact oracle): per-pair status/II/failing indices, the
# energy delta and the first-divergence record are all hard; only the
# memories/sec rates ride the wall clock
FUZZ_HARD = ("status", "ii", "memories", "batch", "failing", "energy",
             "divergence")
FUZZ_TOP_HARD = ("archs", "kernels", "memories", "batch", "seed",
                 "mismatches", "errors", "unmapped")
FUZZ_TIME = ("map_time_s", "exec_time_s", "oracle_time_s", "mem_rate")
FUZZTP_HARD = ("status", "ii", "arch", "memories", "batch", "failing",
               "verdict_match", "stacked_failing",
               "stacked_verdict_match")
FUZZTP_TOP_HARD = ("arch", "memories", "batch", "seq_sample", "seed",
                   "smoke")
FUZZTP_TIME = ("seq_rate", "batched_rate", "stacked_rate",
               "batched_speedup", "stacked_speedup")
# tracing must not perturb solving (same_ii), drop instrumentation
# (spans) or break the trace contract (valid/attr_ok) — all hard; the
# attribution fraction and overhead percentages ride the wall clock
OBS_HARD = ("status", "ii", "same_ii", "spans", "valid", "attr_floor",
            "attr_ok")
OBS_TOP_HARD = ("backend", "min_attribution", "max_disabled_overhead_pct",
                "all_same_ii", "all_attr_ok", "all_valid",
                "disabled_overhead_ok")
OBS_TIME = ("wall_off_s", "wall_on_s")


class Gate:
    def __init__(self, time_tol: float, time_floor: float,
                 check_times: bool = True):
        self.time_tol = time_tol
        self.time_floor = time_floor
        self.check_times = check_times
        self.errors: List[str] = []
        self.checked = 0

    def hard(self, where: str, field: str, cur, base) -> None:
        self.checked += 1
        if cur != base:
            self.errors.append(
                f"{where}: {field} changed {base!r} -> {cur!r}")

    def timed(self, where: str, field: str, cur, base) -> None:
        if not self.check_times:
            return
        if cur is None or base is None:
            return
        self.checked += 1
        if max(cur, base) < self.time_floor:
            return
        ref = max(abs(base), 1e-9)
        if abs(cur - base) / ref > self.time_tol:
            self.errors.append(
                f"{where}: {field} {base}s -> {cur}s exceeds "
                f"±{self.time_tol:.0%}")


def _index_rows(rows: List[Dict]) -> Dict[Tuple, Dict]:
    return {(r.get("cil"), r.get("size"), r.get("backend")): r
            for r in rows}


def check_incremental(cur: List[Dict], base: List[Dict], gate: Gate) -> None:
    cur_ix, base_ix = _index_rows(cur), _index_rows(base)
    missing = sorted(set(map(str, base_ix)) - set(map(str, cur_ix)))
    if missing:
        gate.errors.append(f"incremental_solver: rows missing: {missing}")
    for key, b in base_ix.items():
        c = cur_ix.get(key)
        if c is None:
            continue
        where = "incremental_solver" + str(key)
        for f in INC_HARD:
            if f in b:
                gate.hard(where, f, c.get(f), b.get(f))
        for f in INC_TIME:
            if f in b:
                gate.timed(where, f, c.get(f), b.get(f))


def check_portfolio(cur: List[Dict], base: List[Dict], gate: Gate) -> None:
    def ix(rows):
        return {(r.get("cil"), r.get("size"), r.get("strategy")): r
                for r in rows}
    cur_ix, base_ix = ix(cur), ix(base)
    missing = sorted(set(map(str, base_ix)) - set(map(str, cur_ix)))
    if missing:
        gate.errors.append(f"portfolio: rows missing: {missing}")
    for key, b in base_ix.items():
        c = cur_ix.get(key)
        if c is None:
            continue
        where = "portfolio" + str(key)
        for f in PORT_HARD:
            if f in b:
                gate.hard(where, f, c.get(f), b.get(f))
        for f in PORT_TIME:
            if f in b:
                gate.timed(where, f, c.get(f), b.get(f))


def check_dse(cur: Dict, base: Dict, gate: Gate) -> None:
    cur_pts = {(p["kernel"], p["size"]): p for p in cur.get("points", [])}
    base_pts = {(p["kernel"], p["size"]): p for p in base.get("points", [])}
    missing = sorted(str(k) for k in set(base_pts) - set(cur_pts))
    if missing:
        gate.errors.append(f"dse: points missing: {missing}")
    for key, b in base_pts.items():
        c = cur_pts.get(key)
        if c is None:
            continue
        where = "dse" + str(key)
        for f in DSE_HARD:
            if f in b:
                gate.hard(where, f, c.get(f), b.get(f))
        for f in DSE_TIME:
            gate.timed(where, f, c.get(f), b.get(f))
    gate.hard("dse", "pareto",
              json.dumps(cur.get("pareto"), sort_keys=True),
              json.dumps(base.get("pareto"), sort_keys=True))
    gate.timed("dse", "wall_time_s", cur.get("wall_time_s"),
               base.get("wall_time_s"))


def check_arch_dse(cur: Dict, base: Dict, gate: Gate) -> None:
    cur_pts = {(p["kernel"], p["arch"]): p for p in cur.get("points", [])}
    base_pts = {(p["kernel"], p["arch"]): p for p in base.get("points", [])}
    missing = sorted(str(k) for k in set(base_pts) - set(cur_pts))
    if missing:
        gate.errors.append(f"arch_dse: points missing: {missing}")
    for key, b in base_pts.items():
        c = cur_pts.get(key)
        if c is None:
            continue
        where = "arch_dse" + str(key)
        for f in ARCHDSE_HARD:
            if f in b:
                gate.hard(where, f, c.get(f), b.get(f))
        for f in ARCHDSE_TIME:
            gate.timed(where, f, c.get(f), b.get(f))
    gate.hard("arch_dse", "pareto",
              json.dumps(cur.get("pareto"), sort_keys=True),
              json.dumps(base.get("pareto"), sort_keys=True))
    gate.hard("arch_dse", "acceptance",
              json.dumps(cur.get("acceptance"), sort_keys=True),
              json.dumps(base.get("acceptance"), sort_keys=True))
    gate.timed("arch_dse", "wall_time_s", cur.get("wall_time_s"),
               base.get("wall_time_s"))


def check_serving(cur: Dict, base: Dict, gate: Gate) -> None:
    cur_pts = {(p["kernel"], p["arch"]): p for p in cur.get("points", [])}
    base_pts = {(p["kernel"], p["arch"]): p for p in base.get("points", [])}
    missing = sorted(str(k) for k in set(base_pts) - set(cur_pts))
    if missing:
        gate.errors.append(f"serving: points missing: {missing}")
    for key, b in base_pts.items():
        c = cur_pts.get(key)
        if c is None:
            continue
        where = "serving" + str(key)
        for f in SERVING_HARD:
            if f in b:
                gate.hard(where, f, c.get(f), b.get(f))
    for f in SERVING_TOP_HARD:
        if f in base:
            gate.hard("serving", f, cur.get(f), base.get(f))
    for f in SERVING_TIME:
        c, b = cur.get(f), base.get(f)
        if f.endswith("_ms") and c is not None and b is not None:
            # convert to seconds so the sub-second noise floor applies
            c, b = c / 1e3, b / 1e3
        gate.timed("serving", f, c, b)


def check_fuzz(cur: Dict, base: Dict, gate: Gate) -> None:
    def ix(doc):
        return {(p.get("kernel"), p.get("arch")): p
                for p in doc.get("results", [])}
    cur_ix, base_ix = ix(cur), ix(base)
    missing = sorted(str(k) for k in set(base_ix) - set(cur_ix))
    if missing:
        gate.errors.append(f"fuzz: results missing: {missing}")
    for key, b in base_ix.items():
        c = cur_ix.get(key)
        if c is None:
            continue
        where = "fuzz" + str(key)
        for f in FUZZ_HARD:
            if f in b:
                gate.hard(where, f, c.get(f), b.get(f))
        for f in FUZZ_TIME:
            gate.timed(where, f, c.get(f), b.get(f))
    for f in FUZZ_TOP_HARD:
        if f in base:
            gate.hard("fuzz", f, cur.get(f), base.get(f))


def check_fuzz_throughput(cur: Dict, base: Dict, gate: Gate) -> None:
    cur_ix = {r.get("kernel"): r for r in cur.get("rows", [])}
    base_ix = {r.get("kernel"): r for r in base.get("rows", [])}
    missing = sorted(str(k) for k in set(base_ix) - set(cur_ix))
    if missing:
        gate.errors.append(f"fuzz_throughput: rows missing: {missing}")
    for key, b in base_ix.items():
        c = cur_ix.get(key)
        if c is None:
            continue
        where = f"fuzz_throughput({key})"
        for f in FUZZTP_HARD:
            if f in b:
                gate.hard(where, f, c.get(f), b.get(f))
        for f in FUZZTP_TIME:
            gate.timed(where, f, c.get(f), b.get(f))
    for f in FUZZTP_TOP_HARD:
        if f in base:
            gate.hard("fuzz_throughput", f, cur.get(f), base.get(f))
    for f in ("verdicts_agree", "stacked_verdicts_agree", "ok",
              "mismatch", "unsat_capped", "unmapped", "kernels"):
        gate.hard("fuzz_throughput.summary", f,
                  cur.get("summary", {}).get(f),
                  base.get("summary", {}).get(f))


def check_obs(cur: Dict, base: Dict, gate: Gate) -> None:
    cur_ix = {(c["kernel"], c["arch"]): c for c in cur.get("cases", [])}
    base_ix = {(c["kernel"], c["arch"]): c for c in base.get("cases", [])}
    missing = sorted(str(k) for k in set(base_ix) - set(cur_ix))
    if missing:
        gate.errors.append(f"obs: cases missing: {missing}")
    for key, b in base_ix.items():
        c = cur_ix.get(key)
        if c is None:
            continue
        where = "obs" + str(key)
        for f in OBS_HARD:
            if f in b:
                gate.hard(where, f, c.get(f), b.get(f))
        for f in OBS_TIME:
            gate.timed(where, f, c.get(f), b.get(f))
    for f in OBS_TOP_HARD:
        if f in base:
            gate.hard("obs", f, cur.get(f), base.get(f))
    gate.timed("obs", "wall_time_s", cur.get("wall_time_s"),
               base.get("wall_time_s"))


def check_toolchain_map(cur: Dict, base: Dict, gate: Gate) -> None:
    where = f"toolchain_map({base.get('kernel')}@{base.get('grid')})"
    for f in TOOLMAP_HARD:
        if f in base:
            gate.hard(where, f, cur.get(f), base.get(f))
    for f in TOOLMAP_TIME:
        gate.timed(where, f, cur.get(f), base.get(f))


def correctness_projection(doc) -> bytes:
    """Canonical bytes of every machine-independent field of ``doc``.

    Wall times, cache counters and per-stage timings are excluded; row
    sets are key-sorted so the projection is order-insensitive.  Two
    artifacts with equal projections are interchangeable as far as the
    CI contract is concerned.
    """
    if isinstance(doc, dict) and doc.get("bench") == "dse":
        stable = {
            "points": sorted(
                ({k: p.get(k) for k in ("kernel", "size") + DSE_HARD}
                 for p in doc.get("points", [])),
                key=lambda p: (str(p["kernel"]), str(p["size"]))),
            "pareto": doc.get("pareto"),
        }
    elif isinstance(doc, dict) and doc.get("bench") == "arch_dse":
        stable = {
            "points": sorted(
                ({k: p.get(k) for k in ("kernel", "arch") + ARCHDSE_HARD}
                 for p in doc.get("points", [])),
                key=lambda p: (str(p["kernel"]), str(p["arch"]))),
            "pareto": doc.get("pareto"),
            "acceptance": doc.get("acceptance"),
        }
    elif isinstance(doc, dict) and doc.get("bench") == "toolchain_map":
        stable = {k: doc.get(k) for k in TOOLMAP_HARD}
    elif isinstance(doc, dict) and doc.get("bench") == "serving":
        stable = {
            "points": sorted(
                ({k: p.get(k) for k in ("kernel", "arch") + SERVING_HARD}
                 for p in doc.get("points", [])),
                key=lambda p: (str(p["kernel"]), str(p["arch"]))),
            "summary": {k: doc.get(k) for k in SERVING_TOP_HARD},
        }
    elif isinstance(doc, dict) and doc.get("bench") == "fuzz":
        stable = {
            "results": sorted(
                ({k: p.get(k) for k in ("kernel", "arch") + FUZZ_HARD}
                 for p in doc.get("results", [])),
                key=lambda p: (str(p["kernel"]), str(p["arch"]))),
            "summary": {k: doc.get(k) for k in FUZZ_TOP_HARD},
        }
    elif isinstance(doc, dict) and doc.get("bench") == "obs":
        stable = {
            "cases": sorted(
                ({k: c.get(k) for k in ("kernel", "arch") + OBS_HARD}
                 for c in doc.get("cases", [])),
                key=lambda c: (str(c["kernel"]), str(c["arch"]))),
            "top": {k: doc.get(k) for k in OBS_TOP_HARD},
        }
    elif isinstance(doc, dict) and doc.get("bench") == "fuzz_throughput":
        stable = {
            "rows": sorted(
                ({k: r.get(k) for k in ("kernel",) + FUZZTP_HARD}
                 for r in doc.get("rows", [])),
                key=lambda r: str(r["kernel"])),
            "top": {k: doc.get(k) for k in FUZZTP_TOP_HARD},
            "summary": {
                k: doc.get("summary", {}).get(k)
                for k in ("verdicts_agree", "stacked_verdicts_agree",
                          "ok", "mismatch", "unsat_capped", "unmapped",
                          "kernels")},
        }
    elif (isinstance(doc, list) and doc
          and doc[0].get("bench") == "portfolio"):
        stable = sorted(
            ({k: r.get(k)
              for k in ("cil", "size", "strategy") + PORT_HARD if k in r}
             for r in doc),
            key=lambda r: (str(r.get("cil")), str(r.get("size")),
                           str(r.get("strategy"))))
    elif isinstance(doc, list):
        stable = sorted(
            ({k: r.get(k)
              for k in ("cil", "size", "backend") + INC_HARD if k in r}
             for r in doc),
            key=lambda r: (str(r.get("cil")), str(r.get("size")),
                           str(r.get("backend"))))
    else:
        raise ValueError("unrecognized artifact shape")
    return json.dumps(stable, sort_keys=True,
                      separators=(",", ":")).encode("utf-8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("current")
    ap.add_argument("baseline")
    ap.add_argument("--time-tol", type=float, default=0.25,
                    help="relative wall-time tolerance (default 0.25)")
    ap.add_argument("--time-floor", type=float, default=1.0,
                    help="skip time checks when both sides are below this "
                         "many seconds (noise floor)")
    ap.add_argument("--correctness-only", action="store_true",
                    help="gate only machine-independent fields (the PR CI "
                         "lane); wall-time gating is nightly-only")
    ap.add_argument("--assert-identical", action="store_true",
                    help="additionally require byte-identical correctness "
                         "projections (smoke-baseline contract)")
    args = ap.parse_args(argv)
    with open(args.current) as fh:
        cur = json.load(fh)
    with open(args.baseline) as fh:
        base = json.load(fh)
    gate = Gate(args.time_tol, args.time_floor,
                check_times=not args.correctness_only)
    if isinstance(base, dict) and base.get("bench") == "dse":
        check_dse(cur, base, gate)
    elif isinstance(base, dict) and base.get("bench") == "arch_dse":
        check_arch_dse(cur, base, gate)
    elif isinstance(base, dict) and base.get("bench") == "toolchain_map":
        check_toolchain_map(cur, base, gate)
    elif isinstance(base, dict) and base.get("bench") == "serving":
        check_serving(cur, base, gate)
    elif isinstance(base, dict) and base.get("bench") == "fuzz":
        check_fuzz(cur, base, gate)
    elif isinstance(base, dict) and base.get("bench") == "fuzz_throughput":
        check_fuzz_throughput(cur, base, gate)
    elif isinstance(base, dict) and base.get("bench") == "obs":
        check_obs(cur, base, gate)
    elif (isinstance(base, list) and base
          and base[0].get("bench") == "portfolio"):
        check_portfolio(cur, base, gate)
    elif isinstance(base, list):
        check_incremental(cur, base, gate)
    else:
        print(f"unrecognized baseline shape in {args.baseline}",
              file=sys.stderr)
        return 2
    if args.assert_identical:
        gate.checked += 1
        try:
            if correctness_projection(cur) != correctness_projection(base):
                gate.errors.append(
                    "correctness projections are not byte-identical")
        except ValueError as e:
            gate.errors.append(f"assert-identical: {e}")
    print(f"checked {gate.checked} fields against {args.baseline}")
    if gate.errors:
        print("REGRESSIONS:", file=sys.stderr)
        for e in gate.errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    print("no regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
