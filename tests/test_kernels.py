"""Pallas PE-array kernel vs pure-jnp oracle: shape/value sweeps.

Under ``JAX_PLATFORMS=cpu`` the Pallas kernel runs in interpret mode
(``pe_array.interpret_mode``); tests/test_tpu_compile.py compiles it for a
TPU v5e.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="optional extra: pip install .[test]")
pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
from hypothesis import HealthCheck, given, settings, strategies as st

import jax.numpy as jnp

from repro.cgra import make_grid
from repro.cgra.isa import DST_NONE, Instr, OPCODE, OPS, encode_program
from repro.cgra.simulator import neighbor_table
from repro.kernels.ops import decode_fields, init_state, run_program
from repro.kernels.pe_array import (B_TILE, batch_tile, cycle_step_pallas,
                                    padded_batch)
from repro.kernels.ref import InstrRow, PEState, cycle_step_ref

ALU_OPS = ["SADD", "SSUB", "SMUL", "SLT", "SRT", "SRA", "LAND", "LOR",
           "LXOR", "LNAND", "LNOR", "LXNOR", "BSFA", "BZFA", "BEQ", "MOV",
           "NOP", "LWI", "SWI"]


def random_fields(rng, T, P):
    """Random program; memory ops get collision-free immediate addresses
    (simultaneous same-address stores are UB per the kernels/ref.py
    contract — the mapper can never schedule them)."""
    from repro.cgra.isa import SRC_ZERO
    rows = []
    for t in range(T):
        row = []
        for p in range(P):
            op = rng.choice(ALU_OPS)
            imm = int(rng.randint(0, 64))
            src_a = int(rng.randint(0, 11))
            if op in ("LWI", "SWI"):
                imm = (t * P + p) % 64     # unique address per (t, p)
                src_a = SRC_ZERO
            row.append(Instr(op=op, dst=int(rng.randint(0, 5)) % 4
                             if rng.random() < .7 else DST_NONE,
                             src_a=src_a,
                             src_b=int(rng.randint(0, 11)),
                             imm=imm))
        rows.append(row)
    return rows


@pytest.mark.parametrize("rows_cols,batch,M", [
    ((2, 2), 1, 64), ((2, 2), 8, 128), ((3, 3), 4, 128),
    ((4, 4), 2, 256), ((5, 5), 3, 128),
])
def test_pallas_matches_ref_random_programs(rows_cols, batch, M):
    rng = np.random.RandomState(hash(rows_cols) % 1000 + batch)
    grid = make_grid(*rows_cols)
    P = grid.num_pes
    T = 12
    rows = random_fields(rng, T, P)
    fields = decode_fields(encode_program(rows))
    mem = rng.randint(0, 2**20, size=(batch, M)).astype(np.int32)
    state = init_state(batch, P, mem)
    # seed register/out state so operands are non-trivial
    state = state._replace(
        regs=jnp.asarray(rng.randint(-2**10, 2**10, state.regs.shape),
                         jnp.int32),
        out=jnp.asarray(rng.randint(-2**10, 2**10, state.out.shape),
                        jnp.int32))
    nbrs = neighbor_table(grid)
    f_ref, o_ref = run_program(fields, state, nbrs, backend="ref")
    f_pal, o_pal = run_program(fields, state, nbrs, backend="pallas")
    np.testing.assert_array_equal(np.asarray(o_ref), np.asarray(o_pal))
    for a, b in zip(f_ref, f_pal):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(0, 10_000))
@settings(deadline=None, max_examples=10,
          suppress_health_check=[HealthCheck.too_slow])
def test_pallas_matches_ref_property(seed):
    rng = np.random.RandomState(seed)
    grid = make_grid(2, 2)
    rows = random_fields(rng, 6, 4)
    fields = decode_fields(encode_program(rows))
    state = init_state(2, 4, rng.randint(0, 2**16, size=(2, 64)))
    nbrs = neighbor_table(grid)
    f_ref, o_ref = run_program(fields, state, nbrs, backend="ref")
    f_pal, o_pal = run_program(fields, state, nbrs, backend="pallas")
    np.testing.assert_array_equal(np.asarray(o_ref), np.asarray(o_pal))
    np.testing.assert_array_equal(np.asarray(f_ref.mem), np.asarray(f_pal.mem))


@pytest.mark.parametrize("rows_cols,mem_words,tile", [
    ((2, 2), 128, B_TILE), ((4, 4), 128, B_TILE), ((4, 4), 256, 64),
    ((5, 5), 128, 64), ((6, 6), 128, 64), ((8, 8), 128, 32),
])
def test_batch_tile_shrinks_with_pes_and_memory(rows_cols, mem_words, tile):
    P = rows_cols[0] * rows_cols[1]
    assert batch_tile(P, mem_words) == tile
    assert padded_batch(tile - 1, P, mem_words) == tile - 1   # one block
    assert padded_batch(tile, P, mem_words) == tile
    assert padded_batch(tile + 1, P, mem_words) == 2 * tile
    assert padded_batch(8192, P, mem_words) == 8192


def test_ragged_batch_pallas_matches_ref_through_execute_asm():
    """200 memories are one whole 128-row tile plus 72 rows: the Pallas
    path pads the batch with inert rows and slices it back, so it returns
    exactly the ref path's state and trace for the caller's 200 rows."""
    from repro.cgra.registry import ensure_registered
    from repro.cgra.simulator import execute_asm
    from repro.core.mapper import MapperConfig
    from repro.fuzz.corpus import make_corpus
    from repro.fuzz.engine import batched_oracle, node_values_from_outs
    from repro.toolchain.session import Toolchain

    ensure_registered()
    tc = Toolchain("2x2", MapperConfig(per_ii_timeout_s=60.0,
                                       total_timeout_s=120.0))
    prog = tc.program("bitcount")
    mapping = tc.map(prog).mapping
    asm = tc.assemble(prog, mapping)
    mems = make_corpus("bitcount", 200, seed=5)
    assert padded_batch(200, mapping.grid.num_pes, mems.shape[1]) == 256
    f_ref, o_ref, _ = execute_asm(asm, mapping.grid, mems, batch=200,
                                  backend="ref")
    f_pal, o_pal, _ = execute_asm(asm, mapping.grid, mems, batch=200,
                                  backend="pallas")
    assert o_pal.shape == o_ref.shape == (len(asm.rows), 200, 4)
    np.testing.assert_array_equal(o_ref, o_pal)
    for a, b in zip(f_ref, f_pal):
        assert a.shape[0] == 200
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and both are the oracle's answer, row for row
    oracle_vals, oracle_mem = batched_oracle(prog.builder, mems)
    np.testing.assert_array_equal(np.asarray(f_pal.mem), oracle_mem)
    got = node_values_from_outs(asm, o_pal, prog.builder.trip)
    for n, vals in got.items():
        if n in oracle_vals:
            np.testing.assert_array_equal(vals, oracle_vals[n])


def test_pallas_interprets_only_on_the_cpu_backend():
    import jax

    from repro.kernels.pe_array import interpret_mode
    assert interpret_mode() == (jax.default_backend() == "cpu")


def test_compile_cache_sits_at_a_fixed_ignored_path(monkeypatch):
    """Unset JAX_COMPILATION_CACHE_DIR: the cache goes to .jax_cache in the
    checkout, which git ignores.  Set: the code names no directory."""
    import pathlib

    import jax

    from repro import kernels
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert kernels.CACHE_DIR == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split("\n")
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        kernels.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(kernels.CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(repo / "elsewhere"))
        kernels.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_isa_encode_decode_roundtrip():
    rng = np.random.RandomState(0)
    for _ in range(200):
        ins = Instr(op=str(rng.choice(OPS)), dst=int(rng.randint(0, 8)),
                    src_a=int(rng.randint(0, 11)),
                    src_b=int(rng.randint(0, 11)),
                    imm=int(rng.randint(-2**15, 2**15)))
        assert Instr.decode(ins.encode()) == ins


def test_single_op_semantics_vs_scalar_oracle():
    """Each ALU op on the array == isa.alu_semantics scalarly."""
    from repro.cgra.isa import alu_semantics
    grid = make_grid(2, 2)
    nbrs = neighbor_table(grid)
    rng = np.random.RandomState(3)
    for op in ["SADD", "SSUB", "SMUL", "SLT", "SRT", "SRA", "LAND", "LOR",
               "LXOR", "LNAND", "LNOR", "LXNOR", "BEQ"]:
        a = int(rng.randint(-2**20, 2**20))
        b = int(rng.randint(0, 31)) if op in ("SLT", "SRT", "SRA") \
            else int(rng.randint(-2**20, 2**20))
        rows = [[Instr(op=op, dst=0, src_a=1, src_b=2, imm=0)] * 4]
        fields = decode_fields(encode_program(rows))
        state = init_state(1, 4, np.zeros((1, 16), np.int32))
        regs = np.zeros((1, 4, 4), np.int32)
        regs[:, :, 1] = a
        regs[:, :, 2] = b
        state = state._replace(regs=jnp.asarray(regs))
        final, _ = run_program(fields, state, nbrs, backend="ref")
        got = int(np.asarray(final.out)[0, 0])
        exp = alu_semantics(op, a, b)
        assert got == exp, (op, a, b, got, exp)
