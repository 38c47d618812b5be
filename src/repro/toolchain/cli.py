"""``python -m repro`` — the single user entry point to the toolchain.

Subcommands::

    repro map KERNEL --grid 4x4 [--json] [--out F]   one kernel -> metrics
    repro map KERNEL --arch bordermem-4x4            ... on a hetero spec
    repro serve [--port N | --stdio]                 compile server (repro.serve)
    repro submit KERNEL [--grid 4x4] [--json]        one request to a server
    repro cosim [...]    differential co-simulation (repro.frontend args)
    repro sweep [...]    design-space sweep          (repro.dse args)
    repro fuzz [...]     batched differential fuzzing (repro.fuzz args)
    repro trace [...]    trace report / export / check (repro.obs args)
    repro list [--origin handwritten|traced]         registered kernels
    repro arch list                                  presets + spec grammar
    repro arch show SPEC                             one spec, fully expanded

(The old ``python -m repro.dse`` / ``python -m repro.frontend`` module
entry points are deprecation shims forwarding to ``sweep`` / ``cosim``.)

``map`` compiles one registry kernel end-to-end through a
:class:`~repro.toolchain.session.Toolchain` session and prints either a
human summary or the JSON digest (``--json``); the CI ``toolchain-smoke``
step gates that digest against the committed
``results/BENCH_toolchain_map.json`` baseline.  ``cosim`` and ``sweep``
forward their remaining arguments to the existing ``repro.frontend`` and
``repro.dse`` CLIs unchanged.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional

from ..core.mapper import MapperConfig
from ..kernels import enable_compile_cache
from .session import Toolchain


def _cmd_map(args) -> int:
    cfg = MapperConfig(
        backend=args.backend,
        per_ii_timeout_s=args.timeout / 2,
        total_timeout_s=args.timeout,
        ii_max=args.ii_max,
        strategy=args.strategy,
    )
    if args.trace:
        from ..obs import trace as obs_trace

        obs_trace.enable(args.trace)
    oracle = None if args.no_oracle else "assembler"
    tc = Toolchain(args.arch or args.grid, cfg, cache=args.cache_dir,
                   oracle=oracle)
    t0 = time.monotonic()
    cr = tc.compile(args.kernel, jobs=args.jobs)
    doc = cr.summary()
    doc["bench"] = "toolchain_map"
    doc["oracle"] = tc.oracle_tag
    doc["wall_time_s"] = round(time.monotonic() - t0, 4)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
    if args.json:
        print(json.dumps(doc, indent=1, sort_keys=True))
    else:
        _print_human(cr)
    return 0 if cr.ok else 1


def _print_human(cr) -> None:
    where = cr.arch or cr.size
    if cr.ok:
        m = cr.metrics
        hit = " (cache hit)" if cr.cache_hit else ""
        race = (f" winner={cr.map_result.winner} "
                f"raced={cr.map_result.strategies_raced}"
                if cr.map_result.strategies_raced else "")
        print(
            f"{cr.kernel} @ {where}: II={cr.ii} (mII={cr.mii}) "
            f"backend={cr.map_result.backend} "
            f"cegar={cr.map_result.cegar_rounds}{race}"
        )
        print(
            f"  cycles={m.cycles} energy={m.energy_nj:.2f}nJ "
            f"utilization={m.utilization:.3f} "
            f"map_time={cr.map_time_s:.2f}s{hit}"
        )
    else:
        why = f" — {cr.error}" if cr.error else ""
        print(f"{cr.kernel} @ {where}: {cr.status} at stage {cr.stage!r}{why}")


def _cmd_serve(args) -> int:
    import asyncio

    from ..serve.server import CompileServer

    cfg = MapperConfig(
        backend=args.backend,
        per_ii_timeout_s=args.timeout / 2,
        total_timeout_s=args.timeout,
        ii_max=args.ii_max,
    )
    server = CompileServer(
        args.arch,
        cfg,
        cache=args.cache_dir,
        jobs=args.jobs,
        tenant_budget=args.tenant_budget,
        inline=args.inline,
        oracle=None if args.no_oracle else "assembler",
    )

    from ..serve.protocol import DEFAULT_PORT

    listen_port = args.port if args.port is not None else DEFAULT_PORT

    async def run() -> None:
        if args.stdio:
            await server.serve_stdio()
        else:
            host, port = await server.start(args.host, listen_port)
            print(
                f"repro-serve listening on {host}:{port} "
                f"(jobs={server.jobs}, arch={args.arch})",
                file=sys.stderr,
            )
            await server.wait_closed()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    finally:
        server.close()
    return 0


def _cmd_submit(args) -> int:
    from ..serve.client import request_sync
    from ..serve.protocol import DEFAULT_PORT
    from .artifacts import CompileResult

    port = args.port if args.port is not None else DEFAULT_PORT
    config = {}
    if args.backend != "auto":
        config["backend"] = args.backend
    if args.timeout is not None:
        config["total_timeout_s"] = args.timeout
        config["per_ii_timeout_s"] = args.timeout / 2
    if args.ii_max is not None:
        config["ii_max"] = args.ii_max
    resp = request_sync(
        args.kernel,
        host=args.host,
        port=port,
        shutdown=args.shutdown,
        arch=args.arch or args.grid,
        config=config or None,
        strategy=args.strategy,
        priority=args.priority,
        tenant=args.tenant,
    )
    if resp.get("type") != "result":
        print(json.dumps(resp, indent=1, sort_keys=True), file=sys.stderr)
        return 1
    cr = CompileResult.from_dict(resp["result"])
    doc = cr.summary()
    doc["served"] = resp["served"]
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
    if args.json:
        print(json.dumps(doc, indent=1, sort_keys=True))
    else:
        _print_human(cr)
        print(f"  served={resp['served']}")
    return 0 if cr.ok else 1


def _cmd_arch_list(args) -> int:
    from ..archspec import PRESETS

    print("presets:")
    for name in sorted(PRESETS):
        spec = PRESETS[name]
        print(f"  {name:16s} {spec.to_compact()}")
    print()
    print("spec grammar: TOPOLOGY-RxC[:mem=SEL,mul=SEL,regs=N,ports=K/SCOPE]")
    print("  topologies: torus mesh diagonal one-hop")
    print("  selectors:  all none colK rowK border peA.B.C (+-unions)")
    print("  scopes:     col row global")
    print("  example:    mesh-4x4:mem=col0,regs=8,ports=1/row")
    return 0


def _cmd_arch_show(args) -> int:
    from ..archspec import parse_arch

    spec = parse_arch(args.spec)
    grid = spec.grid()
    print(f"{spec.label()}  ({spec.to_compact()})")
    print(f"  geometry:   {spec.rows}x{spec.cols} ({spec.num_pes} PEs), "
          f"{spec.num_regs} regs/PE")
    print(f"  topology:   {spec.topology} "
          f"(vertex-transitive: {grid.is_vertex_transitive()}, "
          f"assemblable: {spec.assemblable})")
    mem, mul = spec.mem_pes(), spec.mul_pes()
    print(f"  mem PEs:    {'all' if mem is None else sorted(mem)}")
    print(f"  mul PEs:    {'all' if mul is None else sorted(mul)}")
    if spec.ports:
        for label, pes, limit in spec.port_groups():
            print(f"  port {label}: {limit} port(s) over PEs {sorted(pes)}")
    else:
        print("  ports:      unconstrained")
    print(f"  arch hash:  {spec.arch_hash()}")
    # capability map: M = load-store unit, X = multiplier, . = ALU-only
    print("  capability map (M=mem X=mul *=both .=alu):")
    for r in range(spec.rows):
        cells = []
        for c in range(spec.cols):
            p = r * spec.cols + c
            has_mem = mem is None or p in mem
            has_mul = mul is None or p in mul
            cells.append("*" if has_mem and has_mul
                         else "M" if has_mem else "X" if has_mul else ".")
        print("    " + " ".join(cells))
    return 0


def _cmd_list(args) -> int:
    from ..cgra.registry import get_kernel, kernel_names

    names = kernel_names(origin=args.origin or None, variants=True)
    for name in names:
        spec = get_kernel(name)
        variant = (f" (variant of {spec.variant_of})" if spec.variant_of
                   else "")
        print(f"{name:16s} {spec.origin}{variant}")
    print(f"{len(names)} kernels")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    enable_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    # cosim/sweep forward verbatim to the existing sub-CLIs; dispatch
    # before argparse so their own flags (argparse's REMAINDER chokes on
    # a leading dash) and --help reach the right parser
    if argv and argv[0] == "cosim":
        from ..frontend.verify import main as cosim_main

        return cosim_main(argv[1:])
    if argv and argv[0] == "sweep":
        from ..dse.cli import main as sweep_main

        return sweep_main(argv[1:])
    if argv and argv[0] == "fuzz":
        from ..fuzz.cli import main as fuzz_main

        return fuzz_main(argv[1:])
    if argv and argv[0] == "trace":
        from ..obs.cli import main as trace_main

        return trace_main(argv[1:])

    ap = argparse.ArgumentParser(
        prog="python -m repro",
        description="SAT-MapIt toolchain: map, co-simulate, sweep",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    mp = sub.add_parser("map", help="compile one kernel to metrics")
    mp.add_argument("kernel", help="registered kernel name (see: repro list)")
    mp.add_argument("--grid", default="4x4", help="CGRA size (default 4x4)")
    mp.add_argument(
        "--arch",
        default=None,
        help="architecture spec or preset (overrides --grid; "
             "see: repro arch list)",
    )
    mp.add_argument("--backend", default="auto", choices=["auto", "cdcl", "z3"])
    mp.add_argument(
        "--strategy",
        default=None,
        help="solver strategy or portfolio spec (repro.core.backends "
             "grammar): a name like cdcl-seq / z3-atmost, or "
             "portfolio:cdcl-seq+z3-atmost,spec_ii=2, or portfolio:auto; "
             "mutually exclusive with a non-default --backend",
    )
    mp.add_argument(
        "--jobs",
        type=int,
        default=None,
        help="worker processes for a portfolio race "
             "(default: cpu count; 1 = in-process race)",
    )
    mp.add_argument(
        "--timeout",
        type=float,
        default=120.0,
        help="total mapping budget in seconds (default 120)",
    )
    mp.add_argument("--ii-max", type=int, default=32)
    mp.add_argument(
        "--json",
        action="store_true",
        help="print the JSON digest instead of a summary",
    )
    mp.add_argument("--out", default=None, help="also write the digest here")
    mp.add_argument(
        "--cache-dir",
        default=None,
        help="reuse a content-addressed mapping cache",
    )
    mp.add_argument(
        "--no-oracle",
        action="store_true",
        help="disable the assembler CEGAR oracle",
    )
    mp.add_argument(
        "--trace",
        default=None,
        metavar="DIR",
        help="record an obs trace of the compile into DIR "
             "(inspect with: repro trace report DIR)",
    )
    mp.set_defaults(fn=_cmd_map)

    sv = sub.add_parser("serve", help="start the compile server (repro.serve)")
    sv.add_argument("--host", default="127.0.0.1")
    sv.add_argument(
        "--port",
        type=int,
        default=None,
        help="TCP port (default: repro.serve.DEFAULT_PORT; 0 = ephemeral)",
    )
    sv.add_argument(
        "--stdio",
        action="store_true",
        help="serve one connection over stdin/stdout instead of TCP",
    )
    sv.add_argument("--arch", default="4x4",
                    help="default architecture for the hello banner")
    sv.add_argument(
        "--jobs",
        type=int,
        default=None,
        help="warm solver workers (default: cpu count)",
    )
    sv.add_argument(
        "--inline",
        action="store_true",
        help="thread-backed workers instead of processes (no fork; "
             "cooperative deadlines only)",
    )
    sv.add_argument("--backend", default="auto",
                    choices=["auto", "cdcl", "z3"])
    sv.add_argument(
        "--timeout",
        type=float,
        default=120.0,
        help="per-request mapping budget in seconds (default 120)",
    )
    sv.add_argument("--ii-max", type=int, default=32)
    sv.add_argument(
        "--cache-dir",
        default=None,
        help="content-addressed mapping cache shared by all requests",
    )
    sv.add_argument(
        "--tenant-budget",
        type=int,
        default=None,
        help="max concurrently-admitted requests per tenant "
             "(default: unlimited)",
    )
    sv.add_argument(
        "--no-oracle",
        action="store_true",
        help="disable the assembler CEGAR oracle",
    )
    sv.set_defaults(fn=_cmd_serve)

    sb = sub.add_parser("submit", help="send one request to a compile server")
    sb.add_argument("kernel", help="registered kernel name")
    sb.add_argument("--host", default="127.0.0.1")
    sb.add_argument("--port", type=int, default=None)
    sb.add_argument("--grid", default="4x4")
    sb.add_argument("--arch", default=None,
                    help="architecture spec or preset (overrides --grid)")
    sb.add_argument("--backend", default="auto",
                    choices=["auto", "cdcl", "z3"])
    sb.add_argument(
        "--strategy",
        default=None,
        help="solver strategy / portfolio spec (repro.core.backends grammar)",
    )
    sb.add_argument(
        "--timeout",
        type=float,
        default=None,
        help="override the server's mapping budget for this request",
    )
    sb.add_argument("--ii-max", type=int, default=None)
    sb.add_argument("--priority", type=int, default=0,
                    help="queue priority (higher runs sooner)")
    sb.add_argument("--tenant", default="default",
                    help="admission-budget bucket")
    sb.add_argument(
        "--json",
        action="store_true",
        help="print the JSON digest instead of a summary",
    )
    sb.add_argument("--out", default=None, help="also write the digest here")
    sb.add_argument(
        "--shutdown",
        action="store_true",
        help="ask the server to shut down after answering",
    )
    sb.set_defaults(fn=_cmd_submit)

    sub.add_parser(
        "cosim",
        add_help=False,
        help="differential co-simulation (forwards to repro.frontend)",
    )
    sub.add_parser(
        "sweep",
        add_help=False,
        help="design-space sweep (forwards to repro.dse; try --smoke)",
    )
    sub.add_parser(
        "fuzz",
        add_help=False,
        help="batched differential fuzzing fleet (forwards to repro.fuzz)",
    )
    sub.add_parser(
        "trace",
        add_help=False,
        help="trace analysis: report, export --chrome, check (repro.obs)",
    )

    lp = sub.add_parser("list", help="list registered kernels")
    lp.add_argument("--origin", default=None, choices=["handwritten", "traced"])
    lp.set_defaults(fn=_cmd_list)

    arp = sub.add_parser("arch", help="architecture presets and specs")
    arsub = arp.add_subparsers(dest="arch_cmd", required=True)
    al = arsub.add_parser("list", help="presets + the spec grammar")
    al.set_defaults(fn=_cmd_arch_list)
    ash = arsub.add_parser("show", help="expand one spec/preset")
    ash.add_argument("spec", help="spec string or preset name")
    ash.set_defaults(fn=_cmd_arch_show)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
