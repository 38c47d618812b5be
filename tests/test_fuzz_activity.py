"""repro.fuzz.activity: the static-index harvest is bit-identical to a
per-row replay of the routing datapath, kept here as the reference.

The reference walks the T schedule rows, selects every operand from the
register file, the OUT values and the neighbor wiring as the PE array
does, and counts the toggles of each executed cell.  The harvest under
test reads the same statistics as one gather over index pairs worked out
once per schedule.  Both are compared on the schedules of real mappings
(mapping needs no JAX) and of random ones, on random int32 traces that
include INT32_MIN and INT32_MAX, in one block and in several with a
ragged last one; the last test runs ``fuzz_program`` and needs JAX.
"""
import zlib

import numpy as np
import pytest

from repro.cgra.bitstream import AssembledCIL
from repro.cgra.isa import DST_NONE, OPCODE, OPS, SRC_IMM, SRC_OWN, \
    SRC_ZERO, Instr
from repro.cgra.registry import ensure_registered
from repro.core.mapper import MapperConfig
from repro.fuzz import activity
from repro.fuzz.activity import ActivityAccumulator, ActivityReport
from repro.toolchain.session import Toolchain, resolve_arch

ensure_registered()

CFG = MapperConfig(per_ii_timeout_s=60.0, total_timeout_s=120.0, ii_max=32)
KERNELS = ("bitcount", "gsm", "saxpy", "dotprod", "stencil3")
# the II these kernels map at on every grid below; starting there skips
# proving the lower IIs infeasible, which is most of the mapping time
II_START = {"gsm": 5, "saxpy": 4, "stencil3": 7}
# gsm and stencil3 take 4-20 s to map at 6x6, so that grid runs the rest
SCHEDULES = ([("4x4", k) for k in KERNELS]
             + [("mesh-4x4", k) for k in KERNELS]
             + [("6x6", k) for k in ("bitcount", "dotprod", "saxpy")]
             + [("mesh-4x4", "random"), ("6x6", "random")])
# memories of each update call into one accumulator, and the gathered
# elements per block of an update (None: the module's own); 64 elements
# make blocks of 9, 1 and 21 pairs, so several blocks and a ragged last one
BATCHES = {"B1": ((1,), None), "B7": ((7,), None), "B64": ((64,), None),
           "streamed": ((7, 57), None), "blocks": ((7, 57, 3), 64)}
INT32_MIN, INT32_MAX = -(1 << 31), (1 << 31) - 1
M32 = (1 << 32) - 1


class RowReplay:
    """The per-row replay the static-index harvest replaced."""

    def __init__(self, asm: AssembledCIL, grid):
        from repro.cgra.simulator import neighbor_table

        self.asm = asm
        rows = asm.rows
        T, P = len(rows), asm.num_pes
        self.T, self.P = T, P
        self.op = np.array([[OPCODE[ins.op] for ins in row]
                            for row in rows], np.int64)
        self.dst = np.array([[ins.dst for ins in row] for row in rows],
                            np.int64)
        self.sa = np.array([[ins.src_a for ins in row] for row in rows],
                           np.int64)
        self.sb = np.array([[ins.src_b for ins in row] for row in rows],
                           np.int64)
        self.imm = np.array([[ins.imm for ins in row] for row in rows],
                            np.int64)
        self.nbr = np.asarray(neighbor_table(grid), np.int64)
        out0 = np.zeros(P, np.int64)
        regs0 = np.zeros((P, 4), np.int64)
        for pe, val in asm.presets_out.items():
            out0[pe] = np.int64(np.int32(val))
        for (pe, r), val in asm.presets_reg.items():
            regs0[pe, r] = np.int64(np.int32(val))
        self._out0, self._regs0 = out0, regs0
        n_ops = len(OPS)
        self._cells_per_op = np.bincount(self.op.ravel(), minlength=n_ops)
        self._res_bits = np.zeros(n_ops, np.int64)
        self._opnd_bits = np.zeros(n_ops, np.int64)
        self._memories = 0

    def _select(self, sel, regs, out, imm_row):
        B, P = out.shape
        cands = np.empty((11, B, P), np.int64)
        for k in range(4):
            cands[k] = regs[:, :, k]
        cands[SRC_OWN] = out
        for k in range(4):                       # N, E, S, W
            cands[SRC_OWN + 1 + k] = out[:, self.nbr[:, k]]
        cands[SRC_IMM] = np.broadcast_to(imm_row, (B, P))
        cands[SRC_ZERO] = 0
        return cands[sel, :, np.arange(P)].T

    def update(self, outs) -> None:
        x = np.asarray(np.asarray(outs), np.int64) & M32
        outs = x - ((x >= (1 << 31)).astype(np.int64) << 32)
        T, B, P = outs.shape
        prev_out = np.broadcast_to(self._out0, (B, P)).copy()
        regs = np.broadcast_to(self._regs0, (B, P, 4)).copy()
        prev_a = np.zeros((B, P), np.int64)
        prev_b = np.zeros((B, P), np.int64)
        for t in range(T):
            executed = self.op[t] != 0
            a = self._select(self.sa[t], regs, prev_out, self.imm[t])
            b = self._select(self.sb[t], regs, prev_out, self.imm[t])
            res = outs[t]
            tog_res = _xor_bits(res, prev_out).sum(axis=0) * executed
            tog_opnd = (_xor_bits(a, prev_a) + _xor_bits(b, prev_b)) \
                .sum(axis=0) * executed
            np.add.at(self._res_bits, self.op[t], tog_res)
            np.add.at(self._opnd_bits, self.op[t], tog_opnd)
            exec_b = executed[None, :]
            prev_out = np.where(exec_b, res, prev_out)
            prev_a = np.where(exec_b, a, prev_a)
            prev_b = np.where(exec_b, b, prev_b)
            for k in range(4):
                hit = exec_b & (self.dst[t] == k)[None, :]
                regs[:, :, k] = np.where(hit, res, regs[:, :, k])
        self._memories += B

    def report(self) -> ActivityReport:
        op_exec, result_toggle, operand_toggle = {}, {}, {}
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


def _xor_bits(a, b):
    return activity.popcount_u32(((a ^ b) & M32).astype(np.uint32))


def _random_schedule(grid, seed: int) -> AssembledCIL:
    """A schedule of random ops, selectors, write-backs, immediates and
    presets: every selector, edge reads on a mesh included."""
    rng = np.random.default_rng(seed)
    P, T = grid.num_pes, 40
    rows = []
    for _ in range(T):
        row = []
        for _ in range(P):
            op = OPS[int(rng.integers(1, len(OPS)))] \
                if rng.random() < 0.6 else "NOP"
            dst = int(rng.choice([0, 1, 2, 3, DST_NONE]))
            row.append(Instr(op=op, dst=dst,
                             src_a=int(rng.integers(0, 11)),
                             src_b=int(rng.integers(0, 11)),
                             imm=int(rng.integers(-(1 << 15), 1 << 15))))
        rows.append(row)
    presets_out = {int(p): int(rng.integers(INT32_MIN, INT32_MAX + 1))
                   for p in rng.choice(P, P // 2, replace=False)}
    presets_reg = {(int(p), int(r)): int(rng.integers(INT32_MIN,
                                                      INT32_MAX + 1))
                   for p, r in zip(rng.integers(0, P, P),
                                   rng.integers(0, 4, P))}
    return AssembledCIL(name="random", ii=T, num_pes=P, trip=1, rows=rows,
                        prologue=[], kernel=rows, epilogue=[],
                        presets_out=presets_out, presets_reg=presets_reg,
                        node_of_cell={})


@pytest.fixture(scope="module")
def mapped():
    """(asm, grid, program, mapping) per (arch, kernel), each mapped once;
    a random schedule has no program or mapping."""
    toolchains, cache = {}, {}

    def get(arch, kernel):
        if (arch, kernel) not in cache:
            if kernel == "random":
                grid = resolve_arch(arch)
                asm = _random_schedule(grid, zlib.crc32(arch.encode()))
                cache[arch, kernel] = (asm, grid, None, None)
            else:
                tc = toolchains.setdefault(arch, Toolchain(arch, CFG))
                cr = tc.compile(kernel, ii_start=II_START.get(kernel))
                assert cr.ok, f"{arch} {kernel}: {cr.status} ({cr.error})"
                cache[arch, kernel] = (cr.asm, cr.mapping.grid,
                                       cr.program.builder, cr.mapping)
        return cache[arch, kernel]

    return get


@pytest.fixture(scope="module")
def schedule(mapped):
    """(asm, grid) per (arch, kernel)."""
    return lambda arch, kernel: mapped(arch, kernel)[:2]


def _trace(rng, T, B, P) -> np.ndarray:
    """Random int32 trace, a fifth of it INT32_MIN, INT32_MAX, 0 or -1."""
    outs = rng.integers(INT32_MIN, INT32_MAX + 1, size=(T, B, P))
    edge = rng.random(outs.shape) < 0.2
    outs[edge] = rng.choice([INT32_MIN, INT32_MAX, 0, -1], edge.sum())
    return outs.astype(np.int32)


def _assert_same(acc, ref, memories):
    np.testing.assert_array_equal(acc._res_bits, ref._res_bits)
    np.testing.assert_array_equal(acc._opnd_bits, ref._opnd_bits)
    assert acc.report().to_dict() == ref.report().to_dict()
    assert acc.report().memories == memories


@pytest.mark.parametrize("batches,block", list(BATCHES.values()),
                         ids=list(BATCHES))
@pytest.mark.parametrize("arch,kernel", SCHEDULES,
                         ids=[f"{a}-{k}" for a, k in SCHEDULES])
def test_static_gather_matches_row_replay(schedule, monkeypatch, arch,
                                          kernel, batches, block):
    asm, grid = schedule(arch, kernel)
    T, P = len(asm.rows), asm.num_pes
    rng = np.random.default_rng(zlib.crc32(f"{arch}{kernel}{batches}"
                                           .encode()))
    if block is not None:
        monkeypatch.setattr(activity, "_BLOCK_ELEMS", block)
    acc, ref = ActivityAccumulator(asm, grid), RowReplay(asm, grid)
    if block is not None:
        pairs = len(acc.tables.pairs)
        assert any(pairs > block // B and pairs % (block // B)
                   for B in batches), "no update ran a ragged last block"
    for B in batches:
        outs = _trace(rng, T, B, P)
        acc.update(outs)
        ref.update(outs)
    _assert_same(acc, ref, sum(batches))


def test_fleet_chunk_matches_row_replay(schedule):
    """A fleet chunk of 8,192 memories runs the default blocks, the last
    one ragged."""
    asm, grid = schedule("4x4", "gsm")
    T, P, B = len(asm.rows), asm.num_pes, 8192
    acc, ref = ActivityAccumulator(asm, grid), RowReplay(asm, grid)
    step = activity._BLOCK_ELEMS // B
    assert len(acc.tables.pairs) > step and len(acc.tables.pairs) % step
    outs = _trace(np.random.default_rng(8192), T, B, P)
    acc.update(outs)
    ref.update(outs)
    _assert_same(acc, ref, B)


def test_trace_dtypes_read_the_same(schedule):
    """int32, its uint32 view and the int64 of either harvest alike."""
    asm, grid = schedule("4x4", "dotprod")
    outs = _trace(np.random.default_rng(3), len(asm.rows), 9, asm.num_pes)
    reports = []
    for x in (outs, outs.view(np.uint32), outs.astype(np.int64),
              outs.view(np.uint32).astype(np.int64)):
        acc = ActivityAccumulator(asm, grid)
        acc.update(x)
        reports.append(acc.report().to_dict())
    assert reports[1:] == reports[:1] * 3


def test_shape_mismatch_raises(schedule):
    asm, grid = schedule("4x4", "dotprod")
    T, P = len(asm.rows), asm.num_pes
    acc = ActivityAccumulator(asm, grid)
    for shape in ((T + 1, 4, P), (T, 4, P - 1)):
        with pytest.raises(ValueError, match="does not match the schedule"):
            acc.update(np.zeros(shape, np.int32))
    assert acc.report().memories == 0


# ---------------------------------------------------------------------------
# fuzz_program (jax-gated)
# ---------------------------------------------------------------------------


def test_fuzz_program_activity_matches_row_replay(mapped, monkeypatch):
    """The activity a two-chunk ``fuzz_program`` run reports is the
    reference replay of the OUT trace its own seam returned."""
    pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
    from repro.cgra import simulator
    from repro.fuzz.corpus import make_corpus
    from repro.fuzz.engine import fuzz_program

    asm, grid, prog, mapping = mapped("4x4", "dotprod")
    traces = []
    execute_asm = simulator.execute_asm

    def capturing(*args, **kwargs):
        final, outs, out0 = execute_asm(*args, **kwargs)
        traces.append(np.array(outs))
        return final, outs, out0

    monkeypatch.setattr(simulator, "execute_asm", capturing)
    rep = fuzz_program(prog, mapping, make_corpus("dotprod", 16, seed=5),
                       batch=8)
    assert rep.ok and len(traces) == 2
    ref = RowReplay(asm, grid)
    for outs in traces:
        ref.update(outs)
    assert rep.activity == ref.report().to_dict()
