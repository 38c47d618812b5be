#!/usr/bin/env python3
"""Bring-up check of the batched verify path on one TPU chip.

    python3 chip_smoke.py

1. Refuses to run, and exits 2, unless JAX's first device is a TPU.
2. Maps every (kernel, arch) of ``CASES`` through ``Toolchain`` with the
   default sequential strategy and no mapping cache, before any work on
   the device.
3. Fuzzes each mapping with ``repro.fuzz.engine.fuzz_program`` over a
   40,000-memory ``make_corpus`` corpus at batch 8,192 (so the last chunk,
   7,232 memories, is ragged), once with the ref simulator backend and
   once with Pallas, both checked against the numpy oracle.
4. Executes the first and the last chunk on both backends and compares the
   final PE-array state and the whole OUT trace, which holds every node
   value, between them.

Each phase prints one JSON line, labelled with the device kind, with the
backend compiles it caused and how many of them JAX's persistent
compilation cache answered.  Its times are host wall-clock readings of one
run, not benchmark results.  The last line is ``{"ok": true, "device":
{...}}`` only when every case mapped, no memory mismatched the oracle and
the two backends agreed; otherwise it says ``"ok": false`` and the exit
code is 1.
"""
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

SRC = Path(__file__).resolve().parent / "src"
# 4x4 torus: bitcount, dotprod, gsm (CEGAR-active), stencil3 (traced
# front end); one case each on a mesh and on a fabric above 16 PEs
CASES = (("bitcount", "4x4"), ("dotprod", "4x4"), ("gsm", "4x4"),
         ("stencil3", "4x4"), ("stencil3", "mesh-4x4"), ("gsm", "6x6"))
MEMORIES = 40_000
BATCH = 8_192
SEED = 0
BACKENDS = ("ref", "pallas")


def emit(line) -> None:
    print(json.dumps(line), flush=True)


class CompileCounter:
    """Backend compiles (persistent-cache hits included) and cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration_secs

    def since(self, mark):
        compiles, compile_s, hits = mark
        return {"compiles": self.compiles - compiles,
                "compile_s": self.compile_s - compile_s,
                "cache_hits": self.cache_hits - hits}

    def mark(self):
        return self.compiles, self.compile_s, self.cache_hits


def map_cases(kind):
    """(kernel, arch, LoopBuilder, Mapping) per case that mapped, and
    whether every case did."""
    from repro.cgra.registry import ensure_registered
    from repro.core.mapper import MapperConfig
    from repro.toolchain.session import Toolchain

    ensure_registered()
    # the budget `repro fuzz` maps with by default
    cfg = MapperConfig(per_ii_timeout_s=60.0, total_timeout_s=120.0,
                       ii_max=32)
    sessions = {}
    mapped, all_mapped = [], True
    for kernel, arch in CASES:
        if arch not in sessions:
            sessions[arch] = Toolchain(arch, cfg)
        tc = sessions[arch]
        t0 = time.monotonic()
        prog = tc.program(kernel)
        res = tc.map(prog)
        emit({"phase": "map", "kernel": kernel, "arch": arch,
              "status": res.status, "ii": res.ii,
              "map_s": time.monotonic() - t0, "device_kind": kind})
        if res.mapping is None:
            all_mapped = False
        else:
            mapped.append((kernel, arch, prog.builder, res.mapping))
    return mapped, all_mapped


def main() -> int:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r};"
              " nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    try:
        from repro.kernels import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import repro from {SRC}: {e}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    from repro.cgra.bitstream import assemble
    from repro.cgra.simulator import execute_asm
    from repro.fuzz.corpus import make_corpus
    from repro.fuzz.engine import fuzz_program

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    counter = CompileCounter()
    mapped, ok = map_cases(kind)

    corpora = {}
    last_lo = (MEMORIES - 1) // BATCH * BATCH
    for kernel, arch, program, mapping in mapped:
        if kernel not in corpora:
            corpora[kernel] = make_corpus(kernel, MEMORIES, seed=SEED)
        mems = corpora[kernel]
        asm = assemble(program, mapping)
        for backend in BACKENDS:
            mark = counter.mark()
            rep = fuzz_program(program, mapping, mems, batch=BATCH,
                               backend=backend, asm=asm, kernel=kernel,
                               arch=arch)
            ok &= rep.ok
            emit({"phase": "fuzz", "kernel": kernel, "arch": arch,
                  "backend": backend, "status": rep.status, "ii": rep.ii,
                  "memories": rep.memories, "batch": rep.batch,
                  "mismatching_memories": len(rep.failing),
                  "mismatches": rep.mismatches[:2],
                  "exec_time_s": rep.exec_time_s,
                  "oracle_time_s": rep.oracle_time_s,
                  "mem_per_s": rep.mem_rate, **counter.since(mark),
                  "device_kind": kind})
        differences = []
        mark = counter.mark()
        for lo in (0, last_lo):
            chunk = mems[lo:lo + BATCH]
            (f_ref, o_ref, _), (f_pal, o_pal, _) = (
                execute_asm(asm, mapping.grid, chunk, batch=len(chunk),
                            backend=backend) for backend in BACKENDS)
            for name, a, b in zip(f_ref._fields, f_ref, f_pal):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    differences.append(f"final {name}, chunk at {lo}")
            if not np.array_equal(o_ref, o_pal):
                differences.append(f"OUT trace, chunk at {lo}")
        ok &= not differences
        emit({"phase": "ref_vs_pallas", "kernel": kernel, "arch": arch,
              "chunks": [0, last_lo], "differences": differences,
              **counter.since(mark), "device_kind": kind})

    emit({"phase": "total", "cases": len(CASES), "mapped": len(mapped),
          "compiles": counter.compiles, "compile_s": counter.compile_s,
          "cache_hits": counter.cache_hits, "device_kind": kind})
    emit({"ok": bool(ok), "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
