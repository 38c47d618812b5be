"""Switching-activity harvesting from batched PE-array runs.

The static energy model (``repro.cgra.energy``) charges every executed op
its full per-op energy — implicitly assuming reference switching activity
on the operand and result buses.  This module reads the recorded out
traces of a batched run through the *routing* datapath (operand selectors
+ register file + neighbor wiring — no ALU re-execution needed, the
results are the trace) and measures what actually toggled:

* per-op executed-instance counts (cells x memories; NOPs included, so
  fault-free counts equal ``AssembledCIL.op_counts() x B``),
* result-bus toggle rates: Hamming distance between consecutive OUT
  values of each PE, per executed op, as a fraction of 32 bits,
* operand-bus toggle rates: same statistic on the A/B port values each
  executed op actually latched.

Nothing the routing datapath tracks depends on the data: whether a cell
executes is its opcode, the register it writes back is its ``dst``, and
every selector is a static code.  So every value a port latches is a
fixed cell of the OUT trace, a preset, an immediate or zero, and every
toggle statistic is ``popcount(V[i] ^ V[j])`` summed over the batch, for
index pairs ``(i, j)`` into one value array ``V`` that are worked out
once per schedule (:func:`replay_tables`).  Harvesting a chunk is then
one gather, XOR and popcount over those pairs.

``repro.cgra.energy.runtime_metrics(activity=...)`` turns these into an
empirical dynamic-energy estimate: each op's energy scales with its
measured toggle rate relative to the reference rate
(``ACTIVITY_REF = 0.5``, i.e. random data).
"""

from __future__ import annotations

from dataclasses import dataclass
from itertools import chain
from typing import Dict

import numpy as np

from ..cgra.arch import PEGrid
from ..cgra.bitstream import AssembledCIL
from ..cgra.isa import OPCODE, OPS, SRC_IMM, SRC_N, SRC_OWN, SRC_W, SRC_ZERO

M32 = (1 << 32) - 1

try:
    _np_bitcount = np.bitwise_count          # numpy >= 2.0
except AttributeError:                        # pragma: no cover - old numpy
    _np_bitcount = None
    _POP_TABLE = np.array([bin(i).count("1") for i in range(256)],
                          np.uint8)

# gathered elements (pairs x memories) per block of a chunk's harvest
_BLOCK_ELEMS = 1 << 21


def popcount_u32(x: np.ndarray) -> np.ndarray:
    """Per-element popcount of a uint32 array."""
    if _np_bitcount is not None:
        return _np_bitcount(x).astype(np.int64)
    b = np.ascontiguousarray(x).view(np.uint8)  # pragma: no cover
    return _POP_TABLE[b].reshape(x.shape + (4,)).sum(-1).astype(np.int64)


def _row_bits(x: np.ndarray) -> np.ndarray:
    """(n, B) uint32 -> (n,) int64: set bits of each row."""
    if _np_bitcount is not None:
        return _np_bitcount(x).sum(axis=1, dtype=np.int64)
    return popcount_u32(x).sum(axis=1)      # pragma: no cover


@dataclass(frozen=True)
class ReplayTables:
    """Static index pairs of one schedule's toggle statistics.

    A chunk's value array ``V`` (rows, B) holds the OUT-trace cells
    ``(cell_t, cell_p)`` that some pair reads, then the constants
    ``consts`` (presets, immediates, zero) that some pair reads.  Each
    distinct pair ``pairs[u]`` indexes two rows of ``V``; contribution
    ``e`` adds ``pairs[pair_of[e]]``'s set bits to bin ``target[e]``: the
    op code for a result, ``len(OPS)`` + the op code for an operand.
    """

    cell_t: np.ndarray
    cell_p: np.ndarray
    consts: np.ndarray
    pairs: np.ndarray
    pair_of: np.ndarray
    target: np.ndarray


def _last_before(mask: np.ndarray) -> np.ndarray:
    """(T, ...) bool -> (T, ...) int64: the last row before each row in
    which ``mask`` held, else -1."""
    rows = np.arange(len(mask)).reshape((-1,) + (1,) * (mask.ndim - 1))
    last = np.maximum.accumulate(np.where(mask, rows, -1), axis=0)
    return np.concatenate([np.full_like(last[:1], -1), last[:-1]])


def replay_tables(op: np.ndarray, dst: np.ndarray, sa: np.ndarray,
                  sb: np.ndarray, imm: np.ndarray, nbr: np.ndarray,
                  out0: np.ndarray, regs0: np.ndarray) -> ReplayTables:
    """Replay the routing datapath's timeline on slot indices.

    op/dst/sa/sb/imm (T, P), nbr (P, 4) N/E/S/W, out0 (P,), regs0 (P, 4).
    Selection reads the state before row t; the OUT and the latched A/B
    advance only on executed cells, and register ``k`` takes the row's
    result where the cell executed and ``dst == k``, as in
    ``repro.kernels.ref``.  Each of those is the OUT-trace cell of the
    last row before t that wrote it, or its preset, so the whole
    timeline is a running maximum over the rows.
    """
    T, P = op.shape
    n_trace = T * P
    imm_u = imm & M32
    imms = np.unique(imm_u)
    consts = np.concatenate([out0 & M32, regs0.ravel() & M32, imms,
                             [0]]).astype(np.uint32)
    base = n_trace + 5 * P
    zero = base + len(imms)

    pe = np.arange(P)
    executed = op != 0
    # (T, P) the row each cell's OUT, and (T, P, 4) each register, last
    # took a result before row t; -1 reads the preset
    out_row = _last_before(executed)
    reg_row = _last_before(executed[:, :, None]
                           & (dst[:, :, None] == np.arange(4)))
    prev_out = np.where(out_row >= 0, out_row * P + pe, n_trace + pe)
    regs = np.where(reg_row >= 0, reg_row * P + pe[:, None],
                    n_trace + P + 4 * pe[:, None] + np.arange(4))
    cands = np.empty((T, P, 11), np.int64)
    cands[:, :, :4] = regs
    cands[:, :, SRC_OWN] = prev_out
    cands[:, :, SRC_N:SRC_W + 1] = prev_out[:, nbr]
    cands[:, :, SRC_IMM] = base + np.searchsorted(imms, imm_u)
    cands[:, :, SRC_ZERO] = zero
    # (T, P) each cell's latched A and B, then the ones latched at the
    # PE's last executed row before t
    a = np.take_along_axis(cands, sa[:, :, None], axis=2)[:, :, 0]
    b = np.take_along_axis(cands, sb[:, :, None], axis=2)[:, :, 0]
    latched = np.maximum(out_row, 0)
    prev_a = np.where(out_row >= 0,
                      np.take_along_axis(a, latched, axis=0), zero)
    prev_b = np.where(out_row >= 0,
                      np.take_along_axis(b, latched, axis=0), zero)
    cur = np.arange(n_trace).reshape(T, P)

    # each executed cell's three pairs, its result against the previous
    # OUT and its A and B against the previous A and B; a pair of one
    # slot never toggles
    n_ops = len(OPS)
    ops = op[executed]
    lo = np.concatenate([cur[executed], a[executed], b[executed]])
    hi = np.concatenate([prev_out[executed], prev_a[executed],
                         prev_b[executed]])
    target = np.concatenate([ops, n_ops + ops, n_ops + ops])
    moves = lo != hi
    lo, hi = np.minimum(lo, hi)[moves], np.maximum(lo, hi)[moves]
    keys, pair_of = np.unique(lo * (zero + 1) + hi, return_inverse=True)
    # V holds only the slots the distinct pairs read, in slot order
    slots, rows = np.unique(np.divmod(keys, zero + 1), return_inverse=True)
    cells = slots[slots < n_trace]
    return ReplayTables(cell_t=cells // P, cell_p=cells % P,
                        consts=consts[slots[len(cells):] - n_trace],
                        pairs=rows.reshape(2, -1).T,
                        pair_of=pair_of.ravel(), target=target[moves])


@dataclass
class ActivityReport:
    """Aggregated switching statistics of one assembled kernel."""

    kernel: str
    memories: int                       # total memories harvested
    cycles: int                         # schedule rows (T)
    op_exec: Dict[str, int]             # op -> executed instances (x mems)
    result_toggle: Dict[str, float]     # op -> mean result toggle rate
    operand_toggle: Dict[str, float]    # op -> mean operand toggle rate

    def to_dict(self) -> Dict:
        return {
            "kernel": self.kernel,
            "memories": self.memories,
            "cycles": self.cycles,
            "op_exec": dict(sorted(self.op_exec.items())),
            "result_toggle": {k: round(v, 6) for k, v in
                              sorted(self.result_toggle.items())},
            "operand_toggle": {k: round(v, 6) for k, v in
                               sorted(self.operand_toggle.items())},
        }


class ActivityAccumulator:
    """Streams batched out traces into toggle statistics.

    One accumulator per assembled kernel; call :meth:`update` with each
    chunk's out trace (T, B, P) and read :meth:`report` at the end.
    The slot pairs come from :func:`replay_tables`, which mirrors
    ``repro.kernels.ref.operand`` exactly (register file timeline
    included), so the harvested values are the values the ALU ports
    actually saw.  Each accumulator builds its schedule's tables once.
    """

    def __init__(self, asm: AssembledCIL, grid: PEGrid):
        from ..cgra.simulator import neighbor_table

        self.asm = asm
        rows = asm.rows
        T, P = len(rows), asm.num_pes
        self.T, self.P = T, P
        fields = np.fromiter(chain.from_iterable(
            (OPCODE[ins.op], ins.dst, ins.src_a, ins.src_b, ins.imm)
            for row in rows for ins in row), np.int64, count=T * P * 5)
        fields = fields.reshape(T, P, 5)
        nbr = np.asarray(neighbor_table(grid), np.int64)   # (P, 4)
        out0 = np.zeros(P, np.int64)
        regs0 = np.zeros((P, 4), np.int64)
        for pe, val in asm.presets_out.items():
            out0[pe] = np.int64(np.int32(val))
        for (pe, r), val in asm.presets_reg.items():
            regs0[pe, r] = np.int64(np.int32(val))
        self.tables = replay_tables(*np.moveaxis(fields, 2, 0), nbr, out0,
                                    regs0)
        n_ops = len(OPS)
        self._cells_per_op = np.bincount(fields[:, :, 0].ravel(),
                                         minlength=n_ops)
        self._res_bits = np.zeros(n_ops, np.int64)
        self._opnd_bits = np.zeros(n_ops, np.int64)
        self._memories = 0

    def update(self, outs: np.ndarray) -> None:
        """Fold one chunk's out trace (T, B, P) into the statistics."""
        x = np.asarray(outs)
        T, B, P = x.shape
        if (T, P) != (self.T, self.P):
            raise ValueError(
                f"trace shape ({T}, ., {P}) does not match the schedule "
                f"({self.T}, ., {self.P})")
        x = (x.view(np.uint32) if x.dtype in (np.int32, np.uint32)
             else x.astype(np.uint32))
        tables = self.tables
        n_cells = len(tables.cell_t)
        values = np.empty((n_cells + len(tables.consts), B), np.uint32)
        values[:n_cells] = x[tables.cell_t, :, tables.cell_p]
        values[n_cells:] = tables.consts[:, None]
        pairs = tables.pairs
        bits = np.empty(len(pairs), np.int64)
        step = max(1, _BLOCK_ELEMS // max(B, 1))
        for lo in range(0, len(pairs), step):
            block = pairs[lo:lo + step]
            diff = values[block[:, 0]]
            diff ^= values[block[:, 1]]
            bits[lo:lo + step] = _row_bits(diff)
        n_ops = len(OPS)
        totals = np.zeros(2 * n_ops, np.int64)
        np.add.at(totals, tables.target, bits[tables.pair_of])
        self._res_bits += totals[:n_ops]
        self._opnd_bits += totals[n_ops:]
        self._memories += B

    def report(self) -> ActivityReport:
        op_exec: Dict[str, int] = {}
        result_toggle: Dict[str, float] = {}
        operand_toggle: Dict[str, float] = {}
        for code, name in enumerate(OPS):
            cells = int(self._cells_per_op[code])
            if cells == 0:
                continue
            instances = cells * self._memories
            op_exec[name] = instances
            if name == "NOP" or instances == 0:
                continue
            result_toggle[name] = float(self._res_bits[code]) \
                / (32.0 * instances)
            operand_toggle[name] = float(self._opnd_bits[code]) \
                / (64.0 * instances)
        return ActivityReport(
            kernel=self.asm.name, memories=self._memories, cycles=self.T,
            op_exec=op_exec, result_toggle=result_toggle,
            operand_toggle=operand_toggle)


def harvest_activity(asm: AssembledCIL, grid: PEGrid,
                     outs: np.ndarray) -> ActivityReport:
    """One-shot harvest of a single batched run's out trace."""
    acc = ActivityAccumulator(asm, grid)
    acc.update(outs)
    return acc.report()
