"""Pallas TPU kernel for the CGRA PE-array cycle step.

One ``pallas_call`` advances the PE-array state of a batch of independent
memories (the same CIL over different inputs) by one CGRA-cycle, with the
semantics that ``kernels/ref.py`` defines.  The grid walks the batch in
tiles of ``bt`` rows (:func:`batch_tile`).  Each tile holds ``(bt, P)``
blocks of OUT and the flags (batch rows on sublanes, the P PEs on lanes), a
``(bt, P, 4)`` register block and a ``(bt, M)`` data-memory block (memory
words on lanes).

Neighbour reads, operand selection and the ALU are ref.py's own functions
(static column slices and compare-and-select chains).  Loads and stores
use one (bt, P, M) one-hot mask against the memory tile, where the ref step
loops over the PEs on (B, M): the two agree on every cycle the module
contract defines, and the mask sums the values of same-cycle stores to one
address, which the contract leaves undefined.

The kernel runs compiled on an accelerator and interpreted on the CPU
backend (:func:`interpret_mode`).  tests/test_kernels.py checks it against
kernels/ref.py in interpret mode; tests/test_tpu_compile.py compiles it for
a TPU v5e.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..cgra.isa import OPCODE
from .ref import InstrRow, PEState, alu, neighbor_outs, operand

B_TILE = 128  # most batch rows per grid step
# Largest (rows, P, M) one-hot mask a grid step may hold, in int32 words,
# with P padded to whole sublane tiles and M to at least one lane tile:
# 64 rows of a 6x6 array over 128 words.  128 rows of 6x6 need ~21 MiB of
# scoped VMEM against the 16 MiB limit of a TPU v5e.
_TILE_WORDS = 64 * 40 * 128


def batch_tile(num_pes: int, mem_words: int) -> int:
    """Batch rows per grid step: ``B_TILE``, halved until the step's
    one-hot memory mask, and with it the (rows, P, 4) register block whose
    4 pads to a full lane tile, fits ``_TILE_WORDS``."""
    per_row = -(-num_pes // 8) * 8 * max(mem_words, 128)
    rows = B_TILE
    while rows > 8 and rows * per_row > _TILE_WORDS:
        rows //= 2
    return rows


def interpret_mode() -> bool:
    """Pallas has no compiled lowering for the CPU backend: interpret
    there, compile everywhere else."""
    return jax.default_backend() == "cpu"


def padded_batch(batch: int, num_pes: int, mem_words: int) -> int:
    """Smallest batch >= ``batch`` the tiling accepts: up to one tile is a
    single block, beyond that a whole number of tiles."""
    bt = batch_tile(num_pes, mem_words)
    return batch if batch <= bt else -(-batch // bt) * bt


def _cycle_kernel(neighbors: Tuple[Tuple[int, int, int, int], ...],
                  op_ref, dst_ref, sa_ref, sb_ref, imm_ref,
                  regs_ref, out_ref, sf_ref, zf_ref, mem_ref,
                  regs_o, out_o, sf_o, zf_o, mem_o):
    op = op_ref[...]
    dst = dst_ref[...]
    sa = sa_ref[...]
    sb = sb_ref[...]
    imm = imm_ref[...]
    regs = regs_ref[...]
    out = out_ref[...]
    sf = sf_ref[...]
    zf = zf_ref[...]
    mem = mem_ref[...]
    B, P = out.shape
    M = mem.shape[1]

    out_nbr = neighbor_outs(out, neighbors)
    a = operand(sa, regs, out, out_nbr, imm)
    b = operand(sb, regs, out, out_nbr, imm)
    res = alu(op, a, b, sf, zf)

    is_lwi = op == OPCODE["LWI"]
    is_load = (op == OPCODE["LWD"]) | is_lwi
    is_swi = op == OPCODE["SWI"]
    is_store = (op == OPCODE["SWD"]) | is_swi
    addr = a + jnp.where((is_lwi | is_swi)[None, :], imm[None, :], 0)
    addr = jnp.clip(addr, 0, M - 1)
    # one-hot load: (B, P, M) mask against the memory tile
    marange = jax.lax.broadcasted_iota(jnp.int32, (B, P, M), 2)
    onehot = (addr[:, :, None] == marange).astype(jnp.int32)
    loaded = (onehot * mem[:, None, :]).sum(axis=2)
    res = jnp.where(is_load[None, :], loaded, res)
    # one-hot store
    s_mask = onehot * is_store.astype(jnp.int32)[None, :, None]
    any_store = s_mask.sum(axis=1)                         # (B, M)
    store_val = (s_mask * b[:, :, None]).sum(axis=1)       # (B, M)
    mem = jnp.where(any_store > 0, store_val, mem)

    executed = (op != OPCODE["NOP"])[None, :]
    out = jnp.where(executed, res, out)
    sf = jnp.where(executed, (res < 0).astype(jnp.int32), sf)
    zf = jnp.where(executed, (res == 0).astype(jnp.int32), zf)
    regs_o[...] = jnp.stack(
        [jnp.where(executed & (dst == k)[None, :], res, regs[:, :, k])
         for k in range(4)], axis=2)
    out_o[...] = out
    sf_o[...] = sf
    zf_o[...] = zf
    mem_o[...] = mem


def cycle_step_pallas(state: PEState, instr: InstrRow,
                      neighbors) -> PEState:
    """One CGRA-cycle via pl.pallas_call, tiled over the batch axis (the
    batch must be a :func:`padded_batch`)."""
    regs, out, sf, zf, mem = state
    B, P = out.shape
    M = mem.shape[1]
    bt = min(batch_tile(P, M), B)
    if B % bt:
        raise ValueError(f"batch {B} not divisible by tile {bt}")
    grid = (B // bt,)

    def bspec(block, index_map):
        return pl.BlockSpec(block, index_map)

    instr_spec = [bspec((P,), lambda i: (0,))] * 5
    kernel = functools.partial(_cycle_kernel, tuple(map(tuple, neighbors)))
    out_shapes = (
        jax.ShapeDtypeStruct(regs.shape, jnp.int32),
        jax.ShapeDtypeStruct(out.shape, jnp.int32),
        jax.ShapeDtypeStruct(sf.shape, jnp.int32),
        jax.ShapeDtypeStruct(zf.shape, jnp.int32),
        jax.ShapeDtypeStruct(mem.shape, jnp.int32),
    )
    regs_n, out_n, sf_n, zf_n, mem_n = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=instr_spec + [
            bspec((bt, P, 4), lambda i: (i, 0, 0)),
            bspec((bt, P), lambda i: (i, 0)),
            bspec((bt, P), lambda i: (i, 0)),
            bspec((bt, P), lambda i: (i, 0)),
            bspec((bt, M), lambda i: (i, 0)),
        ],
        out_specs=[
            bspec((bt, P, 4), lambda i: (i, 0, 0)),
            bspec((bt, P), lambda i: (i, 0)),
            bspec((bt, P), lambda i: (i, 0)),
            bspec((bt, P), lambda i: (i, 0)),
            bspec((bt, M), lambda i: (i, 0)),
        ],
        out_shape=out_shapes,
        interpret=interpret_mode(),
    )(instr.op, instr.dst, instr.sa, instr.sb, instr.imm,
      regs, out, sf, zf, mem)
    return PEState(regs=regs_n, out=out_n, sf=sf_n, zf=zf_n, mem=mem_n)
