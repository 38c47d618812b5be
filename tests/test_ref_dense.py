"""The dense ref cycle step is bit-identical to the indexed step it
replaced, kept here as the witness.

The witness stacks the operand candidates and the op results and picks one
of each with ``take_along_axis``, gathers loads from the memory and
scatters stores into it.  The step under test (``kernels/ref.py``) picks
all of them by compare-and-select.  Both run on random rows that cover
every opcode, every source selector 0-10 and every destination code, over
random states whose addresses fall in range, below 0 and above the last
word, for one step, for ``run_program`` scans of 32 and of 6 rows and for
one ``run_stacked`` dispatch, on the torus and on the mesh (whose edge PEs
read themselves where the torus wraps).  Two stores to one address in one
cycle are undefined (the module contract), so the rows never hold them.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
pytest.importorskip("hypothesis", reason="optional extra: pip install .[test]")
import jax.numpy as jnp  # noqa: E402
from hypothesis import HealthCheck, given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.cgra import make_grid  # noqa: E402
from repro.cgra.bitstream import AssembledCIL  # noqa: E402
from repro.cgra.isa import (FXP_FRAC_BITS, OPCODE, OPS, SRC_ZERO,  # noqa: E402
                            Instr)
from repro.cgra.simulator import neighbor_table  # noqa: E402
from repro.kernels.ops import run_program  # noqa: E402
from repro.kernels.ref import InstrRow, PEState, cycle_step_ref  # noqa: E402

# the int64 casts of the witness are int32 with x64 off, as they ran
pytestmark = pytest.mark.filterwarnings(
    "ignore:Explicitly requested dtype.*int64:UserWarning")

INT32_MIN, INT32_MAX = -(1 << 31), (1 << 31) - 1
STORES = (OPCODE["SWD"], OPCODE["SWI"])


# --- the indexed step, as kernels/ref.py ran it before the dense form ---

def _select_operand(sel, regs, out, out_nbr, imm):
    B, P = out.shape
    cands = jnp.stack([
        regs[:, :, 0], regs[:, :, 1], regs[:, :, 2], regs[:, :, 3],
        out,
        out_nbr[0], out_nbr[1], out_nbr[2], out_nbr[3],
        jnp.broadcast_to(imm[None, :], (B, P)),
        jnp.zeros((B, P), jnp.int32),
    ], axis=-1)
    sel_b = jnp.broadcast_to(sel[None, :, None], (B, P, 1))
    return jnp.take_along_axis(cands, sel_b, axis=-1)[..., 0]


def _alu(op, a, b, sf, zf):
    shift = b & 31
    a64 = a.astype(jnp.int64)
    b64 = b.astype(jnp.int64)
    results = {
        "NOP": jnp.zeros_like(a),
        "SADD": a + b,
        "SSUB": a - b,
        "SMUL": a * b,
        "FXPMUL": ((a64 * b64) >> FXP_FRAC_BITS).astype(jnp.int32),
        "SLT": a << shift,
        "SRT": jax.lax.shift_right_logical(a, shift),
        "SRA": jax.lax.shift_right_arithmetic(a, shift),
        "LAND": a & b,
        "LOR": a | b,
        "LXOR": a ^ b,
        "LNAND": ~(a & b),
        "LNOR": ~(a | b),
        "LXNOR": ~(a ^ b),
        "BSFA": jnp.where(sf > 0, a, b),
        "BZFA": jnp.where(zf > 0, a, b),
        "LWD": a,
        "LWI": a,
        "SWD": b,
        "SWI": b,
        "BEQ": a - b,
        "BNE": a - b,
        "BLT": a - b,
        "BGE": a - b,
        "JUMP": jnp.zeros_like(a),
        "EXIT": jnp.zeros_like(a),
        "MOV": a + b,
    }
    stacked = jnp.stack([results[name] for name in OPCODE], axis=-1)
    op_b = jnp.broadcast_to(op[None, :, None], a.shape + (1,))
    return jnp.take_along_axis(stacked, op_b, axis=-1)[..., 0]


def _operands(state, instr, neighbors):
    regs, out = state.regs, state.out
    nbr = np.asarray(neighbors)
    out_nbr = [out[:, nbr[:, k]] for k in range(4)]
    return (_select_operand(instr.sa, regs, out, out_nbr, instr.imm),
            _select_operand(instr.sb, regs, out, out_nbr, instr.imm))


def _address(instr, a, M):
    indexed = (instr.op == OPCODE["LWI"]) | (instr.op == OPCODE["SWI"])
    return jnp.clip(a + jnp.where(indexed[None, :], instr.imm[None, :], 0),
                    0, M - 1)


def indexed_step(state, instr, neighbors):
    regs, out, sf, zf, mem = state
    B, P = out.shape
    M = mem.shape[1]
    a, b = _operands(state, instr, neighbors)
    res = _alu(instr.op, a, b, sf, zf)
    is_load = (instr.op == OPCODE["LWD"]) | (instr.op == OPCODE["LWI"])
    is_store = (instr.op == OPCODE["SWD"]) | (instr.op == OPCODE["SWI"])
    addr_c = _address(instr, a, M)
    loaded = jnp.take_along_axis(mem, addr_c, axis=1)
    res = jnp.where(is_load[None, :], loaded, res)
    store_addr = jnp.where(is_store[None, :], addr_c, M)
    mem = mem.at[jnp.arange(B)[:, None], store_addr].set(b, mode="drop")
    executed = (instr.op != OPCODE["NOP"])[None, :]
    out = jnp.where(executed, res, out)
    sf = jnp.where(executed, (res < 0).astype(jnp.int32), sf)
    zf = jnp.where(executed, (res == 0).astype(jnp.int32), zf)
    for k in range(4):
        hit = executed & (instr.dst == k)[None, :]
        regs = regs.at[:, :, k].set(jnp.where(hit, res, regs[:, :, k]))
    return PEState(regs=regs, out=out, sf=sf, zf=zf, mem=mem)


_indexed = jax.jit(indexed_step, static_argnums=2)
_dense = jax.jit(cycle_step_ref, static_argnums=2)
_addresses = jax.jit(lambda st, ins, nbrs: _address(
    ins, _operands(st, ins, nbrs)[0], st.mem.shape[1]), static_argnums=2)


# --- random rows and states ---

def _values(rng, shape, M):
    """Half near the memory (addresses below 0, in range and above the
    last word), half anywhere in int32, the extremes included."""
    near = rng.randint(-M // 2, M + M // 2, size=shape)
    wide = rng.randint(INT32_MIN, INT32_MAX, size=shape, dtype=np.int64)
    wide.flat[:2] = (INT32_MIN, INT32_MAX)
    return np.where(rng.random_sample(shape) < .5, near, wide).astype(np.int32)


def _state(rng, B, P, M):
    return PEState(
        regs=jnp.asarray(_values(rng, (B, P, 4), M)),
        out=jnp.asarray(_values(rng, (B, P), M)),
        sf=jnp.asarray(rng.randint(0, 2, (B, P)), jnp.int32),
        zf=jnp.asarray(rng.randint(0, 2, (B, P)), jnp.int32),
        mem=jnp.asarray(_values(rng, (B, M), M)))


def _row(rng, P, M, t):
    """Row t of a program whose opcodes, selectors and destinations each
    run through every code in turn, shuffled within the row."""
    i = t * P + np.arange(P)

    def codes(n):
        return jnp.asarray(rng.permutation(i % n), jnp.int32)
    return InstrRow(op=codes(len(OPS)), dst=codes(8),
                    sa=codes(SRC_ZERO + 1), sb=codes(SRC_ZERO + 1),
                    imm=jnp.asarray(rng.randint(-M // 2, M + M // 2, P),
                                    jnp.int32))


def _without_store_clashes(state, row, neighbors):
    """The row with every store that would hit, in some memory, an address
    an earlier PE of the row stores to turned into a load."""
    addr = np.asarray(_addresses(state, row, neighbors))
    op = np.asarray(row.op).copy()
    kept = []
    for p in np.flatnonzero(np.isin(op, STORES)):
        if kept and (addr[:, kept] == addr[:, p:p + 1]).any():
            op[p] = OPCODE["LWD"]
        else:
            kept.append(p)
    return row._replace(op=jnp.asarray(op))


def _program(rng, state, neighbors, T, M):
    """T clash-free rows, each checked on the state the rows before it
    leave, with the witness's OUT after each row and its final state."""
    P = state.out.shape[1]
    rows, outs = [], []
    for t in range(T):
        row = _without_store_clashes(state, _row(rng, P, M, t), neighbors)
        state = _indexed(state, row, neighbors)
        rows.append(row)
        outs.append(np.asarray(state.out))
    return rows, np.stack(outs), state


def _assert_same(got, want):
    for name, g, w in zip(PEState._fields, got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


SHAPES = [(n, B, M) for n in (4, 6) for M in (128, 512) for B in (1, 7, 64)]
# small and odd grids, and 64 and 256 words
SMALL_SHAPES = [(2, 1, 64), (2, 8, 128), (3, 4, 128), (4, 2, 256),
                (5, 3, 128)]
TOPOLOGY = pytest.mark.parametrize("torus", [True, False],
                                   ids=["torus", "mesh"])


@TOPOLOGY
@pytest.mark.parametrize("n,B,M", SHAPES)
def test_dense_step_matches_indexed_step(n, B, M, torus):
    rng = np.random.RandomState(n * 1000 + B * 10 + M)
    nbrs = neighbor_table(make_grid(n, n, torus=torus))
    P = n * n
    state = _state(rng, B, P, M)
    # enough rows from one state for every opcode to run once
    for t in range(-(-len(OPS) // P)):
        row = _without_store_clashes(state, _row(rng, P, M, t), nbrs)
        _assert_same(_dense(state, row, nbrs), _indexed(state, row, nbrs))


def _assert_scan_matches(rng, n, B, M, T, torus=True):
    nbrs = neighbor_table(make_grid(n, n, torus=torus))
    state = _state(rng, B, n * n, M)
    rows, want_outs, want = _program(rng, state, nbrs, T, M)
    fields = InstrRow(*(jnp.stack(f) for f in zip(*rows)))
    final, outs = run_program(fields, state, nbrs)
    _assert_same(final, want)
    np.testing.assert_array_equal(np.asarray(outs), want_outs)


@TOPOLOGY
@pytest.mark.parametrize("n,B,M", SHAPES + SMALL_SHAPES)
def test_ref_scan_matches_indexed_steps(n, B, M, torus):
    rng = np.random.RandomState(n * 1000 + B * 10 + M + 1)
    _assert_scan_matches(rng, n, B, M, 32, torus)


@given(st.integers(0, 10_000))
@settings(deadline=None, max_examples=10,
          suppress_health_check=[HealthCheck.too_slow])
def test_ref_scan_matches_indexed_steps_property(seed):
    _assert_scan_matches(np.random.RandomState(seed), 2, 2, 64, 6)


def test_nop_row_keeps_state_and_flags():
    rng = np.random.RandomState(3)
    nbrs = neighbor_table(make_grid(4, 4))
    state = _state(rng, 7, 16, 128)
    row = _row(rng, 16, 128, 0)._replace(
        op=jnp.full((16,), OPCODE["NOP"], jnp.int32))
    _assert_same(_dense(state, row, nbrs), state)


def test_run_stacked_matches_indexed_steps():
    """Two bitstreams of 20 and 32 rows on the 4x4 torus in one vmapped
    dispatch: the shorter is padded with NOP rows."""
    from repro.fuzz.engine import run_stacked
    from repro.cgra.simulator import preset_state

    rng = np.random.RandomState(11)
    grid = make_grid(4, 4)
    nbrs = neighbor_table(grid)
    B, P, M = 7, 16, 128
    mems = _values(rng, (2, B, M), M)
    asms, wants = [], []
    for k, T in enumerate((20, 32)):
        presets_out = {int(p): int(v) for p, v in
                       zip(rng.choice(P, 5, replace=False),
                           _values(rng, (5,), M))}
        presets_reg = {(int(p), int(r)): int(v) for p, r, v in
                       zip(rng.choice(P, 5, replace=False),
                           rng.randint(0, 4, 5), _values(rng, (5,), M))}
        asm = AssembledCIL(
            name=f"random{k}", ii=1, num_pes=P, trip=T, rows=[], prologue=[],
            kernel=[], epilogue=[], presets_out=presets_out,
            presets_reg=presets_reg, node_of_cell={})
        state = preset_state(asm, P, mems[k], B)
        state = jax.tree_util.tree_map(jnp.asarray, state)
        rows, want_outs, want = _program(rng, state, nbrs, T, M)
        asm.rows = [[Instr(op=OPS[int(r.op[p])], dst=int(r.dst[p]),
                           src_a=int(r.sa[p]), src_b=int(r.sb[p]),
                           imm=int(r.imm[p])) for p in range(P)]
                    for r in rows]
        asms.append(asm)
        wants.append((want_outs, want))
    final, outs = run_stacked(asms, grid, mems)
    for k, (want_outs, want) in enumerate(wants):
        _assert_same([f[k] for f in final], want)
        T = len(want_outs)
        np.testing.assert_array_equal(outs[k, :T], want_outs)
        # the NOP rows that pad the shorter bitstream change nothing
        np.testing.assert_array_equal(outs[k, T:],
                                      np.broadcast_to(want_outs[-1],
                                                      outs[k, T:].shape))
