"""The ISA's encode/decode round trip, each ALU op of the PE-array step
against the scalar ISA semantics, and where JAX keeps its compile cache.

tests/test_ref_dense.py holds the step and its scan against an indexed
witness; tests/test_tpu_compile.py compiles the scan for a TPU v5e.
"""
import numpy as np
import pytest

pytest.importorskip("jax", reason="optional extra: pip install .[jax]")

import jax.numpy as jnp

from repro.cgra import make_grid
from repro.cgra.isa import Instr, OPS, encode_program
from repro.cgra.simulator import neighbor_table
from repro.kernels.ops import decode_fields, init_state, run_program


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
        final, _ = run_program(fields, state, nbrs)
        got = int(np.asarray(final.out)[0, 0])
        exp = alu_semantics(op, a, b)
        assert got == exp, (op, a, b, got, exp)
