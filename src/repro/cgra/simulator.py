"""Cycle-accurate execution of mapped CILs + end-to-end verification.

Pipeline: LoopBuilder program -> SAT mapping -> bitstream -> JAX PE-array
execution (the jnp cycle step, scanned) -> per-node value extraction.  The
``verify`` helper compares every node's last-iteration value and the final
data memory against the pure-Python oracle — the strongest possible check of
schedule, routing, register allocation and codegen at once.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.mapping import Mapping
from .arch import PEGrid
from .bitstream import AssembledCIL, assemble
from .programs import LoopBuilder


def map_for_execution(program: LoopBuilder, grid: PEGrid, config=None):
    """SAT-map with the bitstream assembler as a CEGAR oracle: prologue
    clobbers (codegen-level counterexamples the paper's encoding does not
    model) are fed back as blocking clauses.

    Compatibility shim — new code should use the session API instead::

        Toolchain(grid, config).map(program)   # repro.toolchain
    """
    from ..core.mapper import map_dfg
    from ..toolchain.oracles import assembler_oracle

    return map_dfg(program.build_dfg(), grid, config,
                   assemble_check=assembler_oracle(program))


def neighbor_table(grid: PEGrid) -> Tuple[Tuple[int, int, int, int], ...]:
    """(N, E, S, W) neighbor PE ids per PE, honoring the grid's resolved
    topology.

    Only the torus wraps: on a mesh an edge PE has no neighbor in the
    off-grid direction, so the selector is wired back to the PE itself
    (reading it returns the PE's own OUT — the self/ZERO semantics of an
    unconnected port; the assembler never emits such a read, because
    ``_direction`` only resolves PEs that are mapped as adjacent).
    Before this derived from the topology, the table always wrapped, so a
    bitstream executing on a mesh could observe values across the seam
    that the hardware has no wire for.
    """
    wrap = grid.spec.resolved_topology() == "torus"
    rows, cols = grid.spec.rows, grid.spec.cols
    out = []
    for p in range(grid.num_pes):
        r, c = grid.coords(p)
        ids = []
        for dr, dc in ((-1, 0), (0, 1), (1, 0), (0, -1)):   # N, E, S, W
            nr, nc = r + dr, c + dc
            if wrap:
                ids.append(grid.pe_at(nr, nc))
            elif 0 <= nr < rows and 0 <= nc < cols:
                ids.append(nr * cols + nc)
            else:
                ids.append(p)
        out.append(tuple(ids))
    return tuple(out)


@dataclass
class SimResult:
    asm: AssembledCIL
    node_values: Dict[int, np.ndarray]     # node -> (B,) last-iteration value
    final_mem: np.ndarray                  # (B, M)
    total_rows: int


def preset_state(asm: AssembledCIL, num_pes: int, mem: np.ndarray,
                 batch: int):
    """Initial PE-array state for ``asm``: zeros plus the register/output
    presets that seed loop-carried values for iteration 0."""
    # deferred: JAX is an optional extra — mapping (map_for_execution) must
    # work without it; only execution needs the PE-array kernels
    from ..kernels.ops import init_state
    state = init_state(batch, num_pes, mem)
    out0 = np.array(state.out)
    regs0 = np.array(state.regs)
    for pe, val in asm.presets_out.items():
        out0[:, pe] = val
    for (pe, reg), val in asm.presets_reg.items():
        regs0[:, pe, reg] = val
    return state._replace(out=out0, regs=regs0)


def execute_asm(asm: AssembledCIL, grid: PEGrid, mem: np.ndarray,
                batch: int = 1, backend: str = "ref"):
    """Run an already-assembled CIL over ``batch`` memories in one
    dispatch.  Returns ``(final_state, outs (T, B, P), out0 (B, P))`` —
    the shared execution seam under :func:`simulate` and the batched
    fuzzing engine (``repro.fuzz.engine``), which also needs the preset
    initial OUT values for switching-activity harvesting.

    Spans (``repro.obs``): ``verify.seam`` (timed) holds ``verify.decode``,
    ``verify.preset``, ``verify.dispatch`` (trace, compile on a miss,
    enqueue), ``verify.wait`` (the device run) and ``verify.transfer``
    (the OUT trace to the host); ``d2h_bytes`` counts what comes back."""
    # kept only for the seam fakes of tests/bench/test_bench_faults.py
    if backend != "ref":
        raise ValueError(f"unknown simulator backend {backend!r}")
    import jax

    from ..kernels.ops import decode_fields, run_program
    from ..obs import trace as obs_trace

    with obs_trace.timed_span("verify.seam"):
        with obs_trace.span("verify.decode"):
            fields = decode_fields(asm.words())
        with obs_trace.span("verify.preset") as sp:
            state = preset_state(asm, grid.num_pes, mem, batch)
            sp.set(d2h_bytes=state.out.nbytes + state.regs.nbytes)
        out0 = np.array(state.out)
        nbrs = neighbor_table(grid)
        with obs_trace.span("verify.dispatch"):
            final, outs = run_program(fields, state, nbrs)
        with obs_trace.span("verify.wait"):
            jax.block_until_ready((final, outs))
        with obs_trace.span("verify.transfer") as sp:
            outs = np.asarray(outs)
            sp.set(d2h_bytes=outs.nbytes)
    return final, outs, out0


def simulate(program: LoopBuilder, mapping: Mapping, mem: np.ndarray,
             batch: int = 1) -> SimResult:
    asm = assemble(program, mapping)
    final, outs, _ = execute_asm(asm, mapping.grid, mem, batch=batch)
    node_values: Dict[int, np.ndarray] = {}
    last_iter = program.trip - 1
    for (t, pe), (n, j) in asm.node_of_cell.items():
        if j == last_iter:
            node_values[n] = outs[t, :, pe]
    return SimResult(asm=asm, node_values=node_values,
                     final_mem=np.asarray(final.mem),
                     total_rows=len(asm.rows))


def verify(program: LoopBuilder, mapping: Mapping,
           mem: np.ndarray) -> List[str]:
    """Returns a list of mismatch strings (empty == end-to-end correct)."""
    errors: List[str] = []
    mem = np.asarray(mem, np.int32)
    sim = simulate(program, mapping, mem, batch=1)
    oracle_mem = [int(v) for v in mem]
    program_copy = program  # oracle mutates mem list only
    results = program_copy.run_oracle(oracle_mem)
    # oracle per-node values of the last iteration
    oracle_vals = program_copy.last_iteration_values(
        [int(v) for v in mem])
    mask = (1 << 32) - 1
    for n, vals in sim.node_values.items():
        got = int(vals[0]) & mask
        exp = oracle_vals.get(n)
        if exp is None:
            continue
        if got != (exp & mask):
            errors.append(
                f"node {n} ({program.name}): sim {got:#x} != oracle "
                f"{exp & mask:#x}")
    sim_mem = sim.final_mem[0].astype(np.int64) & mask
    for i, v in enumerate(oracle_mem):
        if int(sim_mem[i]) != (v & mask):
            errors.append(f"mem[{i}]: sim {int(sim_mem[i]):#x} != oracle "
                          f"{v & mask:#x}")
    return errors
