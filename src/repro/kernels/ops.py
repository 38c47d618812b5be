"""Jit'd wrappers: run a full instruction grid on the PE-array state.

``run_program`` scans the decoded (T, P) instruction grid over the cycle
step of ``kernels/ref.py``, carrying the PE-array state; batch rides
along vectorized, at any size.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from ..cgra.isa import OPS
from .ref import InstrRow, PEState, cycle_step_ref


def decode_fields(words: np.ndarray) -> InstrRow:
    """(T, P) uint32 bitstream -> stacked int32 instruction fields, as host
    arrays that ``run_program`` uploads with its call.

    The fields are cut out of the words by the layout of ``cgra/isa.py``
    (``Instr.decode``): the opcode field is the opcode id, since
    ``OPCODE[OPS[k]] == k``, and ``imm`` is sign-extended from 16 bits.
    An opcode field with no entry in ``OPS`` raises."""
    w = np.asarray(words, np.uint32)
    op = (w >> 27).astype(np.int32)
    if op.size and op.max() >= len(OPS):
        raise ValueError(f"opcode field {op.max()} names no op "
                         f"(there are {len(OPS)})")
    return InstrRow(
        op=op,
        dst=((w >> 24) & 0x7).astype(np.int32),
        sa=((w >> 20) & 0xF).astype(np.int32),
        sb=((w >> 16) & 0xF).astype(np.int32),
        imm=w.astype(np.uint16).view(np.int16).astype(np.int32))


def init_state(batch: int, num_pes: int, mem: np.ndarray) -> PEState:
    """Zero PE-array state over ``mem``, a (batch, M) or (M,) int32 memory
    image, as host arrays that ``run_program`` uploads with its call."""
    mem = np.asarray(mem, np.int32)
    if mem.ndim == 1:
        mem = np.broadcast_to(mem, (batch,) + mem.shape)
    out = np.zeros((batch, num_pes), np.int32)
    return PEState(regs=np.zeros((batch, num_pes, 4), np.int32), out=out,
                   sf=np.zeros_like(out), zf=np.zeros_like(out), mem=mem)


@functools.partial(jax.jit, static_argnames=("neighbors",))
def run_program(fields: InstrRow, state: PEState, neighbors):
    """Scan all instruction rows. Returns (final state, out trace (T, B, P))."""

    def body(st, row):
        new = cycle_step_ref(st, row, neighbors)
        return new, new.out

    return jax.lax.scan(body, state, fields)
