"""Mismatch triage: shrink, replay, explain, reproduce.

When the batched engine flags divergent memories, this module turns the
bulk verdict into something a human can debug:

* :func:`shrink` — batch-bisection to a single failing memory.  Each
  probe is one batched dispatch over half the current candidate set, so
  a failure among N memories is isolated in O(log N) dispatches, and the
  survivor is re-validated *solo* (batch of one) to rule out
  batch-coupling artifacts.
* :func:`first_divergence` — replays the one failing memory with the
  full out trace and walks the schedule in cycle order against the
  per-iteration oracle values, naming the first (cycle, PE, node,
  iteration) where simulation and oracle part ways.
* :func:`write_reproducer` — a self-contained JSON under
  ``results/fuzz_failures/``: kernel, arch, II, the memory
  image, the divergence, and the verify-style mismatch lines.
* :func:`inject_fault` — the detector's own self-test: flip one
  instruction field of a known-good bitstream so tests can prove the
  fuzzer is able to fail, shrink and explain.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..cgra.bitstream import AssembledCIL, assemble
from ..cgra.isa import Instr
from ..cgra.programs import LoopBuilder
from .engine import (
    M32,
    batched_oracle,
    batched_oracle_iterations,
    compare_batch,
    mismatch_strings,
    node_values_from_outs,
)


@dataclass
class Divergence:
    """First point where the simulated trace leaves the oracle."""

    cycle: int
    pe: int
    node: int
    iteration: int
    got: int
    expected: int

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return (f"cycle {self.cycle}, PE {self.pe}: node {self.node} "
                f"(iteration {self.iteration}) sim {self.got:#x} != "
                f"oracle {self.expected:#x}")


def shrink(
    mems: np.ndarray,
    check: Callable[[np.ndarray], np.ndarray],
    indices: Optional[Sequence[int]] = None,
) -> Tuple[np.ndarray, Optional[int], int]:
    """Bisect a batch with at least one failing memory down to one.

    ``check(mems) -> (B,) bool failing mask`` is the batched probe (one
    engine dispatch).  Returns ``(memory, corpus_index, probes)``; the
    survivor is re-validated alone so the reproducer is guaranteed to
    fail at batch size 1.  Raises ``ValueError`` if the initial batch
    has no failure, or if the failure refuses to reproduce solo (a
    batch-coupling bug — worth reporting by itself).
    """
    mems = np.asarray(mems)
    if mems.ndim == 1:
        mems = mems[None, :]
    idx = (np.arange(mems.shape[0]) if indices is None
           else np.asarray(list(indices)))
    probes = 0
    cur = mems
    if cur.shape[0] == 0:
        raise ValueError("shrink: empty batch")
    while cur.shape[0] > 1:
        half = cur.shape[0] // 2
        probes += 1
        mask = np.asarray(check(cur[:half]), bool)
        if mask.any():
            keep = np.nonzero(mask)[0]
            cur, idx = cur[:half][keep], idx[:half][keep]
        else:
            # the failure lives in the other half; re-probe it
            probes += 1
            mask = np.asarray(check(cur[half:]), bool)
            if not mask.any():
                raise ValueError(
                    "shrink: failure vanished when the batch was split — "
                    "batch-coupled divergence")
            keep = np.nonzero(mask)[0]
            cur, idx = cur[half:][keep], idx[half:][keep]
        # keep only the first survivor: minimality, not a smaller batch
        cur, idx = cur[:1], idx[:1]
    probes += 1
    solo = np.asarray(check(cur), bool)
    if not solo.any():
        raise ValueError(
            "shrink: survivor does not fail at batch size 1 — "
            "batch-coupled divergence")
    return cur[0], int(idx[0]), probes


def engine_check(
    program: LoopBuilder,
    mapping,
    asm: Optional[AssembledCIL] = None,
) -> Callable[[np.ndarray], np.ndarray]:
    """The standard batched probe for :func:`shrink`: execute + oracle +
    compare, returning the failing mask."""
    from ..cgra.simulator import execute_asm

    if asm is None:
        asm = assemble(program, mapping)
    the_asm = asm

    def check(mems: np.ndarray) -> np.ndarray:
        mems = np.asarray(mems, np.int32)
        if mems.ndim == 1:
            mems = mems[None, :]
        final, outs, _ = execute_asm(the_asm, mapping.grid, mems,
                                     batch=mems.shape[0])
        sim_vals = node_values_from_outs(the_asm, outs, program.trip)
        oracle_vals, oracle_mem = batched_oracle(program, mems)
        return compare_batch(sim_vals, np.asarray(final.mem),
                             oracle_vals, oracle_mem)

    return check


def first_divergence(
    program: LoopBuilder,
    mapping,
    mem: np.ndarray,
    asm: Optional[AssembledCIL] = None,
) -> Optional[Divergence]:
    """Replay one memory with the full trace and name the first cell
    whose simulated value differs from the oracle's value for that
    (node, iteration)."""
    from ..cgra.simulator import execute_asm

    if asm is None:
        asm = assemble(program, mapping)
    mem = np.asarray(mem, np.int32).reshape(1, -1)
    _, outs, _ = execute_asm(asm, mapping.grid, mem, batch=1)
    history = batched_oracle_iterations(program, mem)
    for (t, pe) in sorted(asm.node_of_cell):
        n, j = asm.node_of_cell[(t, pe)]
        got = int(outs[t, 0, pe]) & M32
        exp = int(history[j][n][0]) & M32
        if got != exp:
            return Divergence(cycle=t, pe=pe, node=n, iteration=j,
                              got=got, expected=exp)
    return None


def write_reproducer(
    out_dir: str,
    kernel: str,
    arch: str,
    asm: AssembledCIL,
    mem: np.ndarray,
    corpus_index: int,
    divergence: Optional[Divergence],
    mismatches: Sequence[str],
) -> str:
    """A self-contained failure record under ``out_dir``; returns the
    path.  Deterministic content (no timestamps) so CI artifacts diff
    cleanly."""
    os.makedirs(out_dir, exist_ok=True)
    safe_arch = arch.replace("/", "_").replace(":", "_")
    path = os.path.join(out_dir,
                        f"{kernel}__{safe_arch}__mem{corpus_index}.json")
    doc = {
        "kernel": kernel,
        "arch": arch,
        "ii": asm.ii,
        "trip": asm.trip,
        "corpus_index": corpus_index,
        "mem": [int(v) for v in np.asarray(mem).ravel()],
        "divergence": divergence.to_dict() if divergence else None,
        "mismatches": list(mismatches),
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
    return path


def triage_failure(
    program: LoopBuilder,
    mapping,
    mems: np.ndarray,
    rep,
    out_dir: str = "results/fuzz_failures",
    asm: Optional[AssembledCIL] = None,
) -> None:
    """The full mismatch pipeline on a failing :class:`FuzzReport`:
    shrink to one memory, replay for the first divergence, write the
    reproducer, and annotate the report in place."""
    if asm is None:
        asm = assemble(program, mapping)
    check = engine_check(program, mapping, asm=asm)
    failing = np.asarray(rep.failing, int)
    mem, idx, _probes = shrink(np.asarray(mems)[failing], check,
                               indices=failing)
    div = first_divergence(program, mapping, mem, asm=asm)
    final_sim = check(mem.reshape(1, -1))  # noqa: F841 — warm replay
    from ..cgra.simulator import execute_asm

    final, outs, _ = execute_asm(asm, mapping.grid, mem.reshape(1, -1),
                                 batch=1)
    sim_vals = node_values_from_outs(asm, outs, program.trip)
    oracle_vals, oracle_mem = batched_oracle(program, mem.reshape(1, -1))
    lines = mismatch_strings(program, sim_vals, np.asarray(final.mem),
                             oracle_vals, oracle_mem, 0, label=idx)
    rep.divergence = div.to_dict() if div else None
    rep.reproducer = write_reproducer(
        out_dir, rep.kernel, rep.arch, asm, mem, idx, div, lines)


# ---------------------------------------------------------------------------
# fault injection — prove the detector can fail
# ---------------------------------------------------------------------------

_FAULT_SWAPS = {"SADD": "SSUB", "SSUB": "SADD", "LXOR": "LOR",
                "LAND": "LOR", "LOR": "LAND", "SMUL": "SADD"}


def inject_fault(asm: AssembledCIL) -> Tuple[AssembledCIL, Tuple[int, int], str]:
    """Return a copy of ``asm`` with one instruction's opcode flipped
    (e.g. SADD -> SSUB) at the earliest schedule cell that computes a
    DFG node.  Returns (mutated asm, (cycle, pe), mutation label)."""
    for (t, pe) in sorted(asm.node_of_cell):
        ins = asm.rows[t][pe]
        if ins.op in _FAULT_SWAPS:
            new_op = _FAULT_SWAPS[ins.op]
            rows = [list(row) for row in asm.rows]
            rows[t][pe] = Instr(op=new_op, dst=ins.dst, src_a=ins.src_a,
                                src_b=ins.src_b, imm=ins.imm)
            mutated = dataclasses.replace(asm, rows=rows)
            return mutated, (t, pe), f"{ins.op}->{new_op}@t{t}pe{pe}"
    raise ValueError(f"no mutable instruction found in {asm.name}")
