"""Benchmark harness: one module per paper table/figure.

  fig7_table4     — Fig. 7 (II vs SoA vs mII) + Table 4 (mapping time)
  table7_8        — Table 7 (II/U/energy/latency) + Table 8 (vs CPU) +
                    Fig. 11 (Pareto pruning), executed on the JAX simulator
  solver_opts     — beyond-paper SAT encoding/symmetry ablations
  incremental_solver — incremental vs cold-rebuild mapping engine
  dse             — design-space sweep (kernels x CGRA sizes, repro.dse)
  arch_dse        — widened architecture sweep (topology x heterogeneity
                    x size, repro.archspec) + §7 pruning analysis
  frontend_cosim  — traced kernels: map + differential co-simulation
                    (skipped without the jax extra — execution needs the
                    PE-array kernels)
  serving         — mapping-as-a-service: Zipf workload through the
                    compile server (throughput, latency percentiles,
                    dedup/cache-hit contract)
  fuzz_throughput — batched differential fuzzing: sequential vs batched
                    vs kernel-stacked memories/sec + verdict agreement
                    (skipped without the jax extra)
  obs_overhead    — tracing cost (off/on) + attribution on the smoke
                    compiles (repro.obs)

Prints ``name,us_per_call,derived`` CSV per the harness convention and
writes JSON artifacts under results/.  Every lane's wall time (including
failed and skipped ones) also lands machine-readably in
``results/bench_lanes.json`` so "where did the benchmark time go" has a
first-class answer.  A lane that raises is reported as ``failed`` and
the run exits non-zero so CI catches breakage instead of silently
continuing.
"""
from __future__ import annotations

import os
import sys
import time
import traceback


def _run(name, fn):
    t0 = time.monotonic()
    out = fn()
    dt = (time.monotonic() - t0) * 1e6
    return name, dt, out


def main() -> int:
    os.makedirs("results", exist_ok=True)
    rows = []
    failures = []
    lane_walls = []

    def lane(name, fn):
        """Run one benchmark lane; a raising lane fails the whole run
        (non-zero exit) but the remaining lanes still execute.  Every
        lane's wall time is recorded for results/bench_lanes.json."""
        t0 = time.monotonic()
        try:
            fn()
            status = "ok"
        except Exception:
            traceback.print_exc()
            failures.append(name)
            rows.append((name, 0.0, "FAILED"))
            status = "failed"
        lane_walls.append({"lane": name, "status": status,
                           "wall_s": round(time.monotonic() - t0, 3)})

    import json
    reuse = os.environ.get("REPRO_BENCH_REUSE") == "1"

    def lane_fig7():
        from . import fig7_table4
        if reuse and os.path.exists("results/fig7_table4.json"):
            d = json.load(open("results/fig7_table4.json"))
            name, dt, summary = "fig7_table4(cached)", 0.0, d["summary"]
        else:
            name, dt, (_, summary) = _run("fig7_table4", fig7_table4.main)
        rows.append((name, dt, f"sat_at_mii={summary['sat_at_mii']}/"
                     f"{summary['cells']};sat_only="
                     f"{summary['sat_solves_where_heuristic_fails']}"))

    def lane_table7_8():
        from . import table7_8_runtime
        if reuse and os.path.exists("results/table7_8.json"):
            d = json.load(open("results/table7_8.json"))
            name, dt, bench_rows, pa = ("table7_8(cached)", 0.0,
                                        d["rows"], d["pareto"])
        else:
            name, dt, (bench_rows, pa) = _run("table7_8",
                                              table7_8_runtime.main)
        verified = sum(1 for r in bench_rows if r.get("verified"))
        rows.append((name, dt,
                     f"verified={verified};pareto_cover="
                     f"{pa['runtime_pareto_covered_by_compiler']};"
                     f"pruning={pa['pruning_factor']}"))

    def lane_solver_opts():
        from . import solver_opts
        name, dt, srows = _run("solver_opts", solver_opts.main)
        agree = sum(1 for r in srows if r["same_ii_as_baseline"])
        rows.append((name, dt, f"ii_agreement={agree}/{len(srows)}"))

    def lane_incremental():
        from . import incremental_solver
        name, dt, irows = _run("incremental_solver", incremental_solver.main)
        summaries = [r for r in irows if r.get("cil") == "geomean"]

        def _fmt(r):
            out = f"{r['backend']}={r['geomean_speedup']}x"
            if r["geomean_speedup_cegar_active"] is not None:
                out += f"(cegar={r['geomean_speedup_cegar_active']}x)"
            return out
        rows.append((name, dt, "speedup:" + ";".join(map(_fmt, summaries))))

    def lane_portfolio():
        from . import portfolio
        name, dt, prows = _run("portfolio", portfolio.main)
        summary = next(r for r in prows if r.get("cil") == "geomean")
        derived = (f"speedup={summary['geomean_speedup']}x"
                   f"(cegar={summary['geomean_speedup_cegar_active']}x);"
                   f"same_ii={summary['all_same_ii']}")
        rows.append((name, dt, derived))

    def lane_dse():
        from repro.dse.cli import run_smoke
        name, dt, doc = _run("dse", run_smoke)
        s = doc["pareto"]["summary"]
        if doc["errors"]:
            raise RuntimeError(f"dse sweep had {doc['errors']} error points")
        rows.append((name, dt,
                     f"mapped={s['mapped_points']};retained="
                     f"{s['mean_retained_fraction']};pruned="
                     f"{s['mean_pruned_fraction']};cache_hits="
                     f"{doc['cache']['hits']}"))

    def lane_frontend():
        import importlib.util
        if importlib.util.find_spec("jax") is None:
            rows.append(("frontend_cosim", 0.0, "skipped(no-jax)"))
            return
        from repro.frontend.verify import run_all
        name, dt, doc = _run("frontend_cosim",
                             lambda: run_all(seeds=16))
        s = doc["summary"]
        if s["failed"]:
            bad = [k["kernel"] for k in doc["kernels"]
                   if k["status"] not in ("ok", "mapped")]
            raise RuntimeError(f"co-simulation failed for {bad}")
        rows.append((name, dt, f"cosim_ok={s['ok']}/{s['total']};"
                     f"seeds={doc['seeds']};grid={doc['grid']}"))

    def lane_arch_dse():
        from . import arch_dse
        # full lane writes beside the committed baseline, never over it
        name, dt, doc = _run(
            "arch_dse", lambda: arch_dse.main(out="results/arch_dse.json"))
        s = doc["pareto"]["summary"]
        acc = doc["acceptance"]
        rows.append((name, dt,
                     f"mapped={s['mapped_points']};retained="
                     f"{s['mean_retained_fraction']};pruned="
                     f"{s['mean_pruned_fraction']};"
                     f"hetero_ok={acc['count']}/{acc['required']}"))

    def lane_serving():
        from . import serving
        # full lane writes beside the committed baseline, never over it
        name, dt, doc = _run(
            "serving", lambda: serving.main(out="results/serving.json"))
        if not doc["dedup_ok"]:
            raise RuntimeError(
                f"serving dedup contract violated: compiles="
                f"{doc['compiles']} unique={doc['unique_points']}")
        rows.append((name, dt,
                     f"rps={doc['throughput_rps']};p99_ms={doc['p99_ms']};"
                     f"cache_hit={doc['cache_hit_ratio']};"
                     f"dedup_ok={doc['dedup_ok']}"))

    def lane_fuzz():
        import importlib.util
        if importlib.util.find_spec("jax") is None:
            rows.append(("fuzz_throughput", 0.0, "skipped(no-jax)"))
            return
        from . import fuzz_throughput
        # full lane writes beside the committed baseline, never over it
        name, dt, doc = _run(
            "fuzz_throughput",
            lambda: fuzz_throughput.main(out="results/fuzz_throughput.json"))
        s = doc["summary"]
        if s["mismatch"] or not s["verdicts_agree"]:
            raise RuntimeError(
                f"fuzzing found {s['mismatch']} mismatching kernels "
                f"(verdicts_agree={s['verdicts_agree']})")
        rows.append((name, dt,
                     f"ok={s['ok']}/{s['kernels']};speedup="
                     f"{s['geomean_batched_speedup']}x;verdicts_agree="
                     f"{s['verdicts_agree']}"))

    def lane_obs():
        from . import obs_overhead
        # full lane writes beside the committed baseline, never over it
        name, dt, doc = _run(
            "obs_overhead",
            lambda: obs_overhead.main(out="results/obs_overhead.json"))
        if not (doc["all_same_ii"] and doc["all_valid"]):
            raise RuntimeError("tracing perturbed or lost a compile")
        rows.append((name, dt,
                     f"attr_ok={doc['all_attr_ok']};"
                     f"disabled_pct={doc['disabled_overhead_pct']};"
                     f"disabled_ok={doc['disabled_overhead_ok']}"))

    # Host-only lanes first, the lanes that execute on the JAX device
    # (table7_8, frontend_cosim, fuzz_throughput) last: dse, arch_dse and
    # serving fork worker fleets, and a child forked after this process has
    # opened the accelerator runtime inherits a chip that belongs to one
    # process at a time.
    lane("fig7_table4", lane_fig7)
    lane("solver_opts", lane_solver_opts)
    lane("incremental_solver", lane_incremental)
    lane("portfolio", lane_portfolio)
    lane("dse", lane_dse)
    lane("arch_dse", lane_arch_dse)
    lane("serving", lane_serving)
    lane("obs_overhead", lane_obs)
    lane("table7_8", lane_table7_8)
    lane("frontend_cosim", lane_frontend)
    lane("fuzz_throughput", lane_fuzz)

    with open("results/bench_lanes.json", "w") as fh:
        json.dump({"lanes": lane_walls,
                   "total_wall_s": round(sum(lw["wall_s"]
                                             for lw in lane_walls), 3),
                   "failed": failures}, fh, indent=1, sort_keys=True)
        fh.write("\n")

    print("\nname,us_per_call,derived")
    for name, dt, derived in rows:
        print(f"{name},{dt:.0f},{derived}")
    print("\nper-lane wall time (results/bench_lanes.json):")
    for lw in lane_walls:
        print(f"  {lw['lane']:<20}{lw['wall_s']:>9.3f}s  {lw['status']}")
    if failures:
        print(f"\nFAILED lanes: {', '.join(failures)}", file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
