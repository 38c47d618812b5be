"""Batched differential engine: one bitstream, thousands of memories.

Three layers, each replacing a serial hot loop:

* :func:`batched_oracle` — the ``LoopBuilder._interpret`` reference
  vectorized over a ``(B, M)`` memory batch in numpy int64 (wrapped to
  int32 after every op, so it is bit-identical to the serial oracle on
  every input the serial oracle accepts).
* :func:`fuzz_program` — chunks a corpus through
  :func:`repro.cgra.simulator.execute_asm` (the JAX PE-array's batch
  axis), compares every last-iteration node value and the final memory
  image against the batched oracle, and reports per-memory verdicts with
  the exact comparison contract of ``simulator.verify``.
* :func:`run_stacked` / :func:`fuzz_stacked` — stacks NOP-padded
  bitstreams of equal grid size on a leading kernel axis and ``vmap``s
  the scan over it, so one dispatch executes K kernels x B memories.

The oracle side needs numpy only; execution needs the ``jax`` extra.
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..cgra.bitstream import AssembledCIL, assemble
from ..cgra.isa import FXP_FRAC_BITS
from ..cgra.programs import Carry, LoopBuilder, Val
from ..obs import trace as obs_trace

M32 = (1 << 32) - 1
_SIGN = 1 << 31


def _wrap32(x) -> np.ndarray:
    """int64 array -> int64 holding signed-32-bit-wrapped values.

    Device arrays are materialized *before* widening: jax with x64
    disabled would silently truncate an ``astype(int64)`` back to int32
    (with a warning), so the conversion must happen on the numpy side.
    """
    x = np.asarray(np.asarray(x), np.int64) & M32
    return x - ((x >= _SIGN).astype(np.int64) << 32)


def _alu_vec(op: str, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Vectorized ``repro.cgra.isa.alu_semantics`` on int64 arrays that
    hold int32-wrapped values (a/b are already wrapped)."""
    if op in ("SADD", "MOV"):
        return _wrap32(a + b)
    if op == "SSUB":
        return _wrap32(a - b)
    if op == "SMUL":
        return _wrap32(a * b)
    if op == "FXPMUL":
        return _wrap32((a * b) >> FXP_FRAC_BITS)
    if op == "SLT":
        return _wrap32(a << (b & 31))
    if op == "SRT":
        return _wrap32((a & M32) >> (b & 31))
    if op == "SRA":
        return _wrap32(a >> (b & 31))
    if op == "LAND":
        return _wrap32(a & b)
    if op == "LOR":
        return _wrap32(a | b)
    if op == "LXOR":
        return _wrap32(a ^ b)
    if op == "LNAND":
        return _wrap32(~(a & b))
    if op == "LNOR":
        return _wrap32(~(a | b))
    if op == "LXNOR":
        return _wrap32(~(a ^ b))
    if op in ("BEQ", "BNE", "BLT", "BGE"):
        return _wrap32(a - b)
    if op in ("JUMP", "EXIT", "NOP"):
        return np.zeros_like(a)
    raise ValueError(f"no ALU semantics for {op}")


def _gather(mem: np.ndarray, addr: np.ndarray) -> np.ndarray:
    """mem (B, M), addr scalar or (B,) -> (B,) loaded values."""
    if addr.ndim == 0:
        return mem[:, int(addr)].copy()
    return mem[np.arange(mem.shape[0]), addr]


def _scatter(mem: np.ndarray, addr: np.ndarray, val: np.ndarray) -> None:
    if addr.ndim == 0:
        mem[:, int(addr)] = val
    else:
        mem[np.arange(mem.shape[0]), addr] = val


def _batched_interpret(
    program: LoopBuilder, mems: np.ndarray, record_iterations: bool = False
) -> Tuple[Dict[int, np.ndarray], np.ndarray, List[Dict[int, np.ndarray]]]:
    """``LoopBuilder._interpret`` over a (B, M) batch.

    Returns (last-iteration node values, final memories, per-iteration
    node values when requested).  Scalar-valued intermediates (pure
    functions of the induction carries) stay scalar until they meet batch
    data, so the common index arithmetic costs nothing per memory.
    Addresses are range-checked like the serial oracle's Python list
    indexing — every registry kernel computes them from induction
    carries, so a violation is a harness bug, not a finding.
    """
    mems = _wrap32(np.asarray(mems, np.int64))
    if mems.ndim == 1:
        mems = mems[None, :]
    B, M = mems.shape
    dfg = program.build_dfg()
    order = dfg.topo_order()
    carry_vals: Dict[int, np.ndarray] = {
        c.update: np.asarray(np.int64(c.init))  # 0-d; broadcasts on use
        for c in program.carries}
    history: List[Dict[int, np.ndarray]] = []
    vals: Dict[int, np.ndarray] = {}
    for _ in range(program.trip):
        vals = {}
        flags: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for nid in order:
            a, b = program.node_srcs[nid]
            imm = program.node_imm[nid]
            node = dfg.nodes[nid]

            def fetch(operand, use_imm):
                if operand is None:
                    return np.asarray(np.int64(imm if use_imm else 0))
                if isinstance(operand, int):
                    return np.asarray(np.int64(operand))
                if isinstance(operand, Val):
                    return vals[operand.node]
                return carry_vals[operand.update]

            av = fetch(a, a is None and node.op not in ("LWI", "SWI"))
            bv = fetch(b, b is None)
            if node.op in ("LWD", "LWI"):
                addr = av + (imm if node.op == "LWI" else 0)
                if (addr < 0).any() or (addr >= M).any():
                    raise IndexError(
                        f"{program.name}: node {nid} ({node.op}) address "
                        f"outside [0, {M})")
                out = _gather(mems, addr)
            elif node.op in ("SWD", "SWI"):
                addr = av + (imm if node.op == "SWI" else 0)
                if (addr < 0).any() or (addr >= M).any():
                    raise IndexError(
                        f"{program.name}: node {nid} ({node.op}) address "
                        f"outside [0, {M})")
                out = np.broadcast_to(bv, (B,)).astype(np.int64)
                _scatter(mems, addr, out)
            elif node.op in ("BSFA", "BZFA"):
                sign, zero = flags[program.flag_deps[nid]]
                out = np.where(sign if node.op == "BSFA" else zero, av, bv)
                out = np.asarray(out, np.int64)
            else:
                out = _alu_vec(node.op, av, bv)
            vals[nid] = out
            flags[nid] = (out < 0, out == 0)
        for c in program.carries:
            carry_vals[c.update] = vals[c.update]
        if record_iterations:
            history.append({n: np.broadcast_to(v, (B,)).copy()
                            for n, v in vals.items()})
    final = {n: np.broadcast_to(v, (B,)) for n, v in vals.items()}
    return final, mems, history


def batched_oracle(
    program: LoopBuilder, mems: np.ndarray
) -> Tuple[Dict[int, np.ndarray], np.ndarray]:
    """(last-iteration node values {nid: (B,)}, final memories (B, M)) —
    the vectorized replacement for per-seed ``last_iteration_values`` +
    ``run_oracle`` calls."""
    vals, final_mems, _ = _batched_interpret(program, mems)
    return vals, final_mems


def batched_oracle_iterations(
    program: LoopBuilder, mems: np.ndarray
) -> List[Dict[int, np.ndarray]]:
    """Per-iteration node values (one dict per trip iteration) — the
    triage side: lets a divergence replay name the first bad cycle."""
    _, _, history = _batched_interpret(program, mems,
                                       record_iterations=True)
    return history


# ---------------------------------------------------------------------------
# differential comparison (the simulator.verify contract, batched)
# ---------------------------------------------------------------------------


def compare_batch(
    sim_node_values: Dict[int, np.ndarray],
    sim_final_mem: np.ndarray,
    oracle_vals: Dict[int, np.ndarray],
    oracle_mem: np.ndarray,
) -> np.ndarray:
    """Per-memory failure mask (B,) comparing every last-iteration node
    value and the full final memory — exactly what ``simulator.verify``
    checks per seed, vectorized."""
    B = sim_final_mem.shape[0]
    bad = np.zeros(B, bool)
    for n, vals in sim_node_values.items():
        exp = oracle_vals.get(n)
        if exp is None:
            continue
        bad |= (np.asarray(np.asarray(vals), np.int64) & M32) != (exp & M32)
    bad |= (
        (np.asarray(np.asarray(sim_final_mem), np.int64) & M32)
        != (oracle_mem & M32)
    ).any(axis=1)
    return bad


def mismatch_strings(
    program: LoopBuilder,
    sim_node_values: Dict[int, np.ndarray],
    sim_final_mem: np.ndarray,
    oracle_vals: Dict[int, np.ndarray],
    oracle_mem: np.ndarray,
    index: int,
    label: Optional[int] = None,
) -> List[str]:
    """The ``verify``-style mismatch lines for one memory of a batch
    (``index`` picks the row; ``label`` is the corpus-level id)."""
    tag = index if label is None else label
    errors: List[str] = []
    for n, vals in sim_node_values.items():
        exp = oracle_vals.get(n)
        if exp is None:
            continue
        got = int(vals[index]) & M32
        want = int(exp[index]) & M32
        if got != want:
            errors.append(f"mem {tag}: node {n} ({program.name}): "
                          f"sim {got:#x} != oracle {want:#x}")
    sim_mem = np.asarray(np.asarray(sim_final_mem[index]), np.int64) & M32
    ref_mem = np.asarray(np.asarray(oracle_mem[index]), np.int64) & M32
    for addr in np.nonzero(sim_mem != ref_mem)[0]:
        errors.append(f"mem {tag}: mem[{int(addr)}] sim "
                      f"{int(sim_mem[addr]):#x} != oracle "
                      f"{int(ref_mem[addr]):#x}")
    return errors


def node_values_from_outs(
    asm: AssembledCIL, outs: np.ndarray, trip: int
) -> Dict[int, np.ndarray]:
    """Last-iteration per-node values from an out trace (T, B, P)."""
    last = trip - 1
    return {n: outs[t, :, pe]
            for (t, pe), (n, j) in asm.node_of_cell.items() if j == last}


# ---------------------------------------------------------------------------
# batched execution over one kernel
# ---------------------------------------------------------------------------


@dataclass
class FuzzReport:
    """Verdict of one (kernel, arch) fuzz run."""

    kernel: str
    arch: str
    status: str                      # ok | mismatch | unmapped | timeout | error
    ii: Optional[int] = None
    memories: int = 0
    mem_words: int = 0               # words of each memory
    batch: int = 0
    failing: List[int] = field(default_factory=list)   # corpus indices
    mismatches: List[str] = field(default_factory=list)  # capped sample
    error: Optional[str] = None
    map_time_s: float = 0.0
    exec_time_s: float = 0.0
    oracle_time_s: float = 0.0
    mem_rate: float = 0.0            # memories verified per second
    activity: Optional[Dict] = None
    energy: Optional[Dict] = None    # static vs empirical dynamic energy
    reproducer: Optional[str] = None  # path written by triage
    divergence: Optional[Dict] = None

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


_MISMATCH_SAMPLE_CAP = 8


def fuzz_program(
    program: LoopBuilder,
    mapping,
    mems: np.ndarray,
    batch: int = 1024,
    collect_activity: bool = True,
    asm: Optional[AssembledCIL] = None,
    kernel: Optional[str] = None,
    arch: str = "4x4",
) -> FuzzReport:
    """Differentially fuzz one assembled mapping over a corpus.

    Chunks ``mems`` (N, M) into batches of ``batch`` memories, executes
    each chunk in one PE-array dispatch, runs the batched oracle on the
    same chunk, and compares under the ``verify`` contract.  Activity
    statistics are harvested from the recorded out traces on the fly.

    Spans (``repro.obs``): one ``verify.job`` per call (with the loop's
    ``trip`` and the memories' ``mem_words``), a ``verify.chunk`` per
    chunk (with its ``rows``, ``pes`` and ``mem_words``) holding the
    seam's ``verify.seam`` (see ``execute_asm``), ``verify.nodes``,
    ``verify.transfer`` (the final memories to the host, with
    ``d2h_bytes``), ``verify.oracle``, ``verify.compare`` and
    ``verify.activity``.  ``exec_time_s`` and ``oracle_time_s`` are the
    summed durations of the timed ``verify.seam`` and ``verify.oracle``.
    """
    from ..cgra.simulator import execute_asm

    from .activity import ActivityAccumulator

    with obs_trace.span("verify.job", kernel=kernel or program.name) as job, \
            obs_trace.tally() as seconds:
        if asm is None:
            asm = assemble(program, mapping)
        mems = np.asarray(mems, np.int32)
        if mems.ndim == 1:
            mems = mems[None, :]
        n = mems.shape[0]
        rep = FuzzReport(kernel=kernel or program.name, arch=arch,
                         status="ok", ii=asm.ii, memories=n,
                         mem_words=mems.shape[1],
                         batch=min(batch, n) if n else batch)
        job.set(memories=n, batch=rep.batch, trip=program.trip,
                mem_words=mems.shape[1])
        acc = (ActivityAccumulator(asm, mapping.grid) if collect_activity
               else None)
        t_total0 = time.monotonic()
        for lo in range(0, n, batch):
            chunk = mems[lo:lo + batch]
            with obs_trace.span("verify.chunk", memories=chunk.shape[0],
                                rows=len(asm.rows),
                                pes=mapping.grid.num_pes,
                                mem_words=chunk.shape[1]):
                final, outs, _ = execute_asm(asm, mapping.grid, chunk,
                                             batch=chunk.shape[0])
                with obs_trace.span("verify.nodes"):
                    sim_vals = node_values_from_outs(asm, outs, program.trip)
                with obs_trace.span("verify.transfer") as sp:
                    sim_mem = np.asarray(final.mem)
                    sp.set(d2h_bytes=sim_mem.nbytes)
                with obs_trace.timed_span("verify.oracle"):
                    oracle_vals, oracle_mem = batched_oracle(program, chunk)
                with obs_trace.span("verify.compare"):
                    bad = compare_batch(sim_vals, sim_mem, oracle_vals,
                                        oracle_mem)
                for i in np.nonzero(bad)[0]:
                    rep.failing.append(lo + int(i))
                    if len(rep.mismatches) < _MISMATCH_SAMPLE_CAP:
                        rep.mismatches.extend(mismatch_strings(
                            program, sim_vals, sim_mem, oracle_vals,
                            oracle_mem, int(i),
                            label=lo + int(i))[:_MISMATCH_SAMPLE_CAP])
                if acc is not None:
                    with obs_trace.span("verify.activity"):
                        acc.update(outs)
        wall = time.monotonic() - t_total0
        rep.exec_time_s = round(seconds["verify.seam"], 4)
        rep.oracle_time_s = round(seconds["verify.oracle"], 4)
        rep.mem_rate = round(n / wall, 2) if wall > 0 and n else 0.0
        rep.mismatches = rep.mismatches[:_MISMATCH_SAMPLE_CAP]
        if rep.failing:
            rep.status = "mismatch"
        if acc is not None:
            rep.activity = acc.report().to_dict()
    return rep


def fuzz_kernel(
    name: str,
    arch: str = "4x4",
    memories: int = 1024,
    batch: int = 1024,
    seed: int = 0,
    shrink: bool = False,
    config=None,
    cache=None,
    failures_dir: str = "results/fuzz_failures",
    strategies: Optional[Sequence[str]] = None,
) -> FuzzReport:
    """Map one registry kernel on ``arch`` and fuzz it end-to-end:
    corpus -> batched differential run -> (on mismatch, optionally)
    shrink + divergence replay + reproducer JSON -> activity-based
    energy delta."""
    from ..core.mapper import MapperConfig
    from ..toolchain.session import Toolchain

    from .corpus import make_corpus
    from .triage import triage_failure

    cfg = config or MapperConfig(per_ii_timeout_s=60.0,
                                 total_timeout_s=120.0, ii_max=32)
    tc = Toolchain(arch, cfg, cache=cache)
    arch_name = tc.arch or f"{tc.grid.spec.rows}x{tc.grid.spec.cols}"
    prog = tc.program(name)
    t0 = time.monotonic()
    try:
        res = tc.map(prog)
    except Exception as e:                     # pragma: no cover - defensive
        return FuzzReport(kernel=name, arch=arch_name, status="error",
                          error=f"{type(e).__name__}: {e}")
    map_time = round(time.monotonic() - t0, 3)
    if res.mapping is None:
        status = "timeout" if res.status == "timeout" else "unmapped"
        return FuzzReport(kernel=name, arch=arch_name, status=status,
                          map_time_s=map_time)
    mems = make_corpus(name, memories, seed=seed, strategies=strategies)
    rep = fuzz_program(prog.builder, res.mapping, mems, batch=batch,
                       kernel=name, arch=arch_name)
    rep.map_time_s = map_time
    if rep.activity is not None:
        rep.energy = _energy_delta(prog.builder, res.mapping, rep.activity)
    if rep.failing and shrink:
        triage_failure(prog.builder, res.mapping, mems, rep,
                       out_dir=failures_dir)
    return rep


def _energy_delta(program, mapping, activity: Dict) -> Dict:
    """Static vs activity-based dynamic energy for one mapping."""
    from ..cgra.energy import metrics_for_mapping

    static = metrics_for_mapping(program, mapping)
    empirical = metrics_for_mapping(program, mapping, activity=activity)
    delta = empirical.dynamic_nj - static.dynamic_nj
    pct = (100.0 * delta / static.dynamic_nj) if static.dynamic_nj else 0.0
    return {
        "static_dynamic_nj": round(static.dynamic_nj, 4),
        "empirical_dynamic_nj": round(empirical.dynamic_nj, 4),
        "delta_nj": round(delta, 4),
        "delta_pct": round(pct, 2),
        "static_total_nj": round(static.energy_nj, 4),
        "empirical_total_nj": round(empirical.energy_nj, 4),
    }


# ---------------------------------------------------------------------------
# kernel stacking: K bitstreams of equal grid size, one vmap'd dispatch
# ---------------------------------------------------------------------------


def _pad_fields(fields, total_rows: int):
    """NOP-pad decoded instruction fields (T, P) to ``total_rows`` rows.
    NOP rows leave all state untouched, so padding at the end is inert."""
    import jax.numpy as jnp

    from ..cgra.isa import DST_NONE, SRC_ZERO
    from ..kernels.ref import InstrRow

    T, P = fields.op.shape
    pad = total_rows - T
    if pad == 0:
        return fields
    z = jnp.zeros((pad, P), jnp.int32)
    return InstrRow(
        op=jnp.concatenate([fields.op, z]),
        dst=jnp.concatenate([fields.dst, jnp.full((pad, P), DST_NONE,
                                                  jnp.int32)]),
        sa=jnp.concatenate([fields.sa, jnp.full((pad, P), SRC_ZERO,
                                                jnp.int32)]),
        sb=jnp.concatenate([fields.sb, jnp.full((pad, P), SRC_ZERO,
                                                jnp.int32)]),
        imm=jnp.concatenate([fields.imm, z]))


def run_stacked(
    asms: Sequence[AssembledCIL],
    grid,
    mems: np.ndarray,
):
    """Execute K same-grid bitstreams over (K, B, M) memories in one
    ``vmap``-ed dispatch.  Returns (final PEState with a leading K axis,
    outs (K, T_max, B, P)).  Shorter bitstreams are NOP-padded: rows past
    a kernel's real schedule execute nothing, so its ``node_of_cell``
    indices stay valid."""
    import jax

    from ..cgra.simulator import neighbor_table, preset_state
    from ..kernels.ops import decode_fields, run_program

    mems = np.asarray(mems, np.int32)
    if mems.ndim == 2:
        mems = np.broadcast_to(mems[None], (len(asms),) + mems.shape)
    K, B, M = mems.shape
    if K != len(asms):
        raise ValueError(f"{len(asms)} bitstreams but {K} memory groups")
    P = grid.num_pes
    for asm in asms:
        if asm.num_pes != P:
            raise ValueError(
                f"cannot stack {asm.name}: {asm.num_pes} PEs != grid {P}")
    fields = [decode_fields(asm.words()) for asm in asms]
    t_max = max(f.op.shape[0] for f in fields)
    fields = [_pad_fields(f, t_max) for f in fields]
    stacked_fields = jax.tree_util.tree_map(
        lambda *xs: jax.numpy.stack(xs), *fields)
    states = [preset_state(asm, P, mems[k], B)
              for k, asm in enumerate(asms)]
    stacked_state = jax.tree_util.tree_map(
        lambda *xs: jax.numpy.stack(xs), *states)
    nbrs = neighbor_table(grid)

    def run_one(f, s):
        return run_program(f, s, nbrs)

    final, outs = jax.vmap(run_one)(stacked_fields, stacked_state)
    return final, np.asarray(outs)


def fuzz_stacked(
    programs: Sequence[LoopBuilder],
    mappings: Sequence,
    mems: np.ndarray,
    arch: str = "4x4",
) -> List[FuzzReport]:
    """Differentially fuzz K same-grid kernels in one stacked dispatch.
    ``mems`` is (B, M) (shared corpus) or (K, B, M).  Oracle comparison
    and verdicts are identical to per-kernel :func:`fuzz_program`."""
    grid = mappings[0].grid
    asms = [assemble(p, m) for p, m in zip(programs, mappings)]
    mems = np.asarray(mems, np.int32)
    if mems.ndim == 2:
        mems = np.broadcast_to(mems[None], (len(asms),) + mems.shape)
    t0 = time.monotonic()
    final, outs = run_stacked(asms, grid, mems)
    exec_time = time.monotonic() - t0
    reports: List[FuzzReport] = []
    for k, (program, asm) in enumerate(zip(programs, asms)):
        sim_vals = node_values_from_outs(asm, outs[k], program.trip)
        sim_mem = np.asarray(final.mem[k])
        t1 = time.monotonic()
        oracle_vals, oracle_mem = batched_oracle(program, mems[k])
        oracle_time = time.monotonic() - t1
        bad = compare_batch(sim_vals, sim_mem, oracle_vals, oracle_mem)
        rep = FuzzReport(
            kernel=program.name, arch=arch, status="ok", ii=asm.ii,
            memories=int(mems.shape[1]), batch=int(mems.shape[1]),
            exec_time_s=round(exec_time / len(asms), 4),
            oracle_time_s=round(oracle_time, 4))
        share = exec_time / len(asms) + oracle_time
        rep.mem_rate = round(mems.shape[1] / share, 2) if share > 0 else 0.0
        for i in np.nonzero(bad)[0]:
            rep.failing.append(int(i))
            if len(rep.mismatches) < _MISMATCH_SAMPLE_CAP:
                rep.mismatches.extend(mismatch_strings(
                    program, sim_vals, sim_mem, oracle_vals, oracle_mem,
                    int(i))[:_MISMATCH_SAMPLE_CAP])
        rep.mismatches = rep.mismatches[:_MISMATCH_SAMPLE_CAP]
        if rep.failing:
            rep.status = "mismatch"
        reports.append(rep)
    return reports
