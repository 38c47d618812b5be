"""Jit'd wrappers: run a full instruction grid on the PE-array state.

``run_program`` scans the decoded (T, P) instruction grid over the cycle
step of ``kernels/ref.py``, carrying the PE-array state; batch rides
along vectorized, at any size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..cgra.isa import decode_program
from .ref import InstrRow, PEState, cycle_step_ref


def decode_fields(words: np.ndarray) -> InstrRow:
    """(T, P) uint32 bitstream -> stacked int32 instruction fields."""
    rows = decode_program(words)
    from ..cgra.isa import OPCODE
    T = len(rows)
    P = len(rows[0]) if T else 0
    op = np.zeros((T, P), np.int32)
    dst = np.zeros((T, P), np.int32)
    sa = np.zeros((T, P), np.int32)
    sb = np.zeros((T, P), np.int32)
    imm = np.zeros((T, P), np.int32)
    for t, row in enumerate(rows):
        for p, ins in enumerate(row):
            op[t, p] = OPCODE[ins.op]
            dst[t, p] = ins.dst
            sa[t, p] = ins.src_a
            sb[t, p] = ins.src_b
            imm[t, p] = ins.imm
    return InstrRow(op=jnp.asarray(op), dst=jnp.asarray(dst),
                    sa=jnp.asarray(sa), sb=jnp.asarray(sb),
                    imm=jnp.asarray(imm))


def init_state(batch: int, num_pes: int, mem: np.ndarray) -> PEState:
    """mem: (batch, M) or (M,) int32 initial memory image."""
    mem = np.asarray(mem, np.int32)
    if mem.ndim == 1:
        mem = np.broadcast_to(mem, (batch,) + mem.shape)
    return PEState(
        regs=jnp.zeros((batch, num_pes, 4), jnp.int32),
        out=jnp.zeros((batch, num_pes), jnp.int32),
        sf=jnp.zeros((batch, num_pes), jnp.int32),
        zf=jnp.zeros((batch, num_pes), jnp.int32),
        mem=jnp.asarray(mem))


@functools.partial(jax.jit, static_argnames=("neighbors",))
def run_program(fields: InstrRow, state: PEState, neighbors):
    """Scan all instruction rows. Returns (final state, out trace (T, B, P))."""

    def body(st, row):
        new = cycle_step_ref(st, row, neighbors)
        return new, new.out

    return jax.lax.scan(body, state, fields)
