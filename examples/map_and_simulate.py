"""End-to-end: C-level loop -> SAT mapping -> bitstream -> JAX CGRA run.

Maps the bitcount benchmark on a 2x2 OpenEdgeCGRA, assembles the
prologue/kernel/epilogue control words, executes them cycle-accurately on
the JAX PE-array simulator, and checks the result
against the Python oracle.

  PYTHONPATH=src python examples/map_and_simulate.py [--benchmark NAME]
"""
import argparse

import numpy as np

from repro.cgra import make_grid
from repro.cgra.bitstream import assemble
from repro.cgra.programs import BENCHMARKS
from repro.cgra.simulator import map_for_execution, simulate
from repro.core import MapperConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--benchmark", default="bitcount",
                    choices=sorted(BENCHMARKS))
    ap.add_argument("--size", type=int, default=2)
    args = ap.parse_args()

    prog = BENCHMARKS[args.benchmark]()
    grid = make_grid(args.size, args.size)
    res = map_for_execution(prog, grid, MapperConfig(per_ii_timeout_s=60))
    print(f"{args.benchmark}: II={res.ii} (mII={res.mii}) on "
          f"{args.size}x{args.size}")
    asm = assemble(prog, res.mapping)
    print(f"bitstream: {len(asm.prologue)} prologue + {len(asm.kernel)} "
          f"kernel + {len(asm.epilogue)} epilogue rows; "
          f"first kernel words: "
          f"{[hex(w) for w in asm.kernel_words()[0][:4]]}")
    mem = np.zeros(128, np.int32)
    sim = simulate(prog, res.mapping, mem, batch=1)
    oracle = prog.run_oracle([0] * 128)
    for name, nid in prog.result_nodes.items():
        got = int(sim.node_values[nid][0])
        print(f"result {name}: CGRA={got}  oracle={oracle[name]}  "
              f"{'OK' if got == oracle[name] else 'MISMATCH'}")


if __name__ == "__main__":
    main()
