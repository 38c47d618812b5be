"""The CGRA PE-array cycle step, in pure jnp.

This module defines the PE-array semantics: given the decoded instruction
row and the PE-array state, advance one CGRA-cycle.  All integer ALU ops
are int32 with wrap-around; flags (sign/zero) are per-PE and updated by
every executed non-NOP op; BSFA/BZFA select between their operands based
on the *pre-cycle* flags (i.e. the flags of the previous instruction on
that PE, as in OpenEdgeCGRA).  FXPMUL is ``(a * b) >> FXP_FRAC_BITS`` on
the int32 product (the high bits of the 64-bit product are lost).  A
selector that names no source (SRC_ZERO, or a code above it) reads zero.

The step is dense: operands, ALU result, loads and stores are each chosen
by elementwise compare-and-select over values the step already holds (a
selector, an opcode or a word index), so it compiles to no gather and no
scatter.

Two simultaneous stores to the same address in one cycle are undefined
behaviour in the ISA (real hardware serializes them through the column
port; the mapper never schedules them); the step here keeps the value of
the highest-numbered PE.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cgra.isa import (FXP_FRAC_BITS, OPCODE, SRC_E, SRC_IMM, SRC_N,
                        SRC_OWN, SRC_R0, SRC_S, SRC_W)


class PEState(NamedTuple):
    regs: jax.Array   # (B, P, 4) int32
    out: jax.Array    # (B, P) int32
    sf: jax.Array     # (B, P) int32 (0/1) sign flag
    zf: jax.Array     # (B, P) int32 (0/1) zero flag
    mem: jax.Array    # (B, M) int32


class InstrRow(NamedTuple):
    op: jax.Array     # (P,) int32 opcode ids
    dst: jax.Array    # (P,) int32
    sa: jax.Array     # (P,) int32 source selectors
    sb: jax.Array     # (P,) int32
    imm: jax.Array    # (P,) int32


def neighbor_outs(out, neighbors):
    """OUT of every PE's (N, E, S, W) neighbour: four (B, P) arrays built
    from static column slices, since the topology is compile-time."""
    nbr = np.asarray(neighbors)
    return [jnp.concatenate([out[:, q:q + 1] for q in nbr[:, k]], axis=1)
            for k in range(4)]


def operand(sel, regs, out, out_nbr, imm):
    """sel: (P,), state tensors batched (B, ...). Returns (B, P) int32."""
    sel = sel[None, :]
    val = jnp.zeros_like(out)
    for k in range(4):
        val = jnp.where(sel == SRC_R0 + k, regs[:, :, k], val)
    val = jnp.where(sel == SRC_OWN, out, val)
    for k, src in enumerate((SRC_N, SRC_E, SRC_S, SRC_W)):
        val = jnp.where(sel == src, out_nbr[k], val)
    return jnp.where(sel == SRC_IMM, imm[None, :], val)


def alu(op, a, b, sf, zf):
    """All-op ALU, selected by opcode. op: (P,), a/b/sf/zf: (B, P).  Loads
    read zero here (the memory path replaces them), as do NOP, JUMP and
    EXIT."""
    shift = b & 31
    prod = a * b
    results = (
        (("SADD", "MOV"), a + b),
        (("SSUB", "BEQ", "BNE", "BLT", "BGE"), a - b),
        (("SMUL",), prod),
        (("FXPMUL",), prod >> FXP_FRAC_BITS),
        (("SLT",), a << shift),
        (("SRT",), jax.lax.shift_right_logical(a, shift)),
        (("SRA",), jax.lax.shift_right_arithmetic(a, shift)),
        (("LAND",), a & b),
        (("LOR",), a | b),
        (("LXOR",), a ^ b),
        (("LNAND",), ~(a & b)),
        (("LNOR",), ~(a | b)),
        (("LXNOR",), ~(a ^ b)),
        (("BSFA",), jnp.where(sf > 0, a, b)),
        (("BZFA",), jnp.where(zf > 0, a, b)),
        (("SWD", "SWI"), b),          # a store's result is the stored value
    )
    acc = jnp.zeros_like(a)
    for names, val in results:
        hit = functools.reduce(jnp.logical_or,
                               [op == OPCODE[n] for n in names])
        acc = jnp.where(hit[None, :], val, acc)
    return acc


def cycle_step_ref(state: PEState, instr: InstrRow,
                   neighbors: Tuple[Tuple[int, int, int, int], ...]) -> PEState:
    """One CGRA-cycle. ``neighbors[p] = (N, E, S, W)`` is static topology."""
    regs, out, sf, zf, mem = state
    B, P = out.shape
    out_nbr = neighbor_outs(out, neighbors)
    a = operand(instr.sa, regs, out, out_nbr, instr.imm)
    b = operand(instr.sb, regs, out, out_nbr, instr.imm)

    res = alu(instr.op, a, b, sf, zf)

    # memory: loads read pre-cycle memory; stores commit at end of cycle
    is_lwi = instr.op == OPCODE["LWI"]
    is_load = (instr.op == OPCODE["LWD"]) | is_lwi
    is_swi = instr.op == OPCODE["SWI"]
    is_store = (instr.op == OPCODE["SWD"]) | is_swi
    M = mem.shape[1]
    addr = a + jnp.where((is_lwi | is_swi)[None, :], instr.imm[None, :], 0)
    addr_c = jnp.clip(addr, 0, M - 1)
    # one PE at a time over (B, M): the word index against the PE's address
    words = jax.lax.broadcasted_iota(jnp.int32, (B, M), 1)
    hits = [words == addr_c[:, p:p + 1] for p in range(P)]
    loaded = jnp.concatenate(
        [jnp.sum(jnp.where(hit, mem, 0), axis=1, keepdims=True)
         for hit in hits], axis=1)
    res = jnp.where(is_load[None, :], loaded, res)
    for p, hit in enumerate(hits):
        mem = jnp.where(hit & is_store[p], b[:, p:p + 1], mem)

    executed = (instr.op != OPCODE["NOP"])[None, :]
    out = jnp.where(executed, res, out)
    sf = jnp.where(executed, (res < 0).astype(jnp.int32), sf)
    zf = jnp.where(executed, (res == 0).astype(jnp.int32), zf)
    for k in range(4):
        hit = executed & (instr.dst == k)[None, :]
        regs = regs.at[:, :, k].set(jnp.where(hit, res, regs[:, :, k]))
    return PEState(regs=regs, out=out, sf=sf, zf=zf, mem=mem)
