"""Plain reference of the CIL semantics the verify path is held to.

It interprets a kernel's loop (the program's ``LoopBuilder``: its nodes,
operands, immediates, flag producers, loop-carried values and trip count)
over a batch of memories, one node at a time in dependency order, with
every value held as a signed 32-bit word:

* SADD/MOV, SSUB, SMUL wrap modulo 2**32; FXPMUL is ``(a*b) >> frac_bits``
  of the exact product, wrapped; SLT, SRT (logical), SRA shift by
  ``b & 31``; the six logic ops act bitwise; BEQ/BNE/BLT/BGE yield
  ``a - b``; JUMP, EXIT and NOP yield 0;
* LWI/LWD load ``mem[a + imm]`` / ``mem[a]``; SWI/SWD store ``b`` there
  and yield it;
* BSFA/BZFA yield ``a`` when the sign/zero flag of their flag producer's
  value (this iteration) is set, else ``b``;
* an absent first operand reads the immediate (zero for LWI/SWI), an
  absent second operand reads the immediate;
* loop-carried operands read the producer's value of the previous
  iteration, or the carry's initial value in the first.

Nodes with no dependency between them run in ascending id order.  The
registry kernels that the cells run store only to words they never load
in the same iteration, so that order does not change their results.

``arithmetic="float32"`` is the control: the same interpreter with
add, subtract, multiply and compare carried out in float32, the nearest
lower precision, which breaks the 32-bit exactness the configuration
states.  The module imports nothing of the program under test.
"""
from __future__ import annotations

import heapq
from typing import Dict, List, Tuple

import numpy as np

M32 = (1 << 32) - 1
SIGN = 1 << 31
_ARITH = ("SADD", "MOV", "SSUB", "SMUL", "FXPMUL", "BEQ", "BNE", "BLT",
          "BGE")


def wrap32(x: np.ndarray) -> np.ndarray:
    """int64 values -> the signed 32-bit words they wrap to (as int64)."""
    x = np.asarray(x, np.int64) & M32
    return x - ((x >= SIGN).astype(np.int64) << 32)


def _is_value(operand) -> bool:      # ``Val``: this iteration's node value
    return hasattr(operand, "node")


def _is_carry(operand) -> bool:      # ``Carry``: loop-carried value
    return hasattr(operand, "update")


def node_order(kernel) -> List[int]:
    """Dependency order of one iteration, ties broken by ascending id."""
    deps: Dict[int, set] = {n.id: set() for n in kernel.nodes}
    for nid, (a, b) in kernel.node_srcs.items():
        for operand in (a, b):
            if _is_value(operand):
                deps[nid].add(operand.node)
    for consumer, producer in kernel.flag_deps.items():
        deps[consumer].add(producer)
    users: Dict[int, List[int]] = {n: [] for n in deps}
    for nid, ds in deps.items():
        for d in ds:
            users[d].append(nid)
    waiting = {n: len(ds) for n, ds in deps.items()}
    ready = [n for n, w in waiting.items() if w == 0]
    heapq.heapify(ready)
    order = []
    while ready:
        n = heapq.heappop(ready)
        order.append(n)
        for u in users[n]:
            waiting[u] -= 1
            if waiting[u] == 0:
                heapq.heappush(ready, u)
    if len(order) != len(deps):
        raise ValueError(f"{kernel.name}: dependency cycle in one iteration")
    return order


def _arith(op: str, a, b, frac_bits: int, arithmetic: str):
    if arithmetic == "float32":
        a = np.asarray(a).astype(np.float32)
        b = np.asarray(b).astype(np.float32)
    if op in ("SADD", "MOV"):
        r = a + b
    elif op == "SMUL":
        r = a * b
    elif op == "FXPMUL":
        r = (a * b) // (1 << frac_bits) if arithmetic == "float32" \
            else (a * b) >> frac_bits
    else:                                     # SSUB and the compares
        r = a - b
    if arithmetic == "float32":
        r = np.asarray(r, np.float64).astype(np.int64)
    return wrap32(r)


def _alu(op: str, a, b, frac_bits: int, arithmetic: str):
    if op in _ARITH:
        return _arith(op, a, b, frac_bits, arithmetic)
    if op == "SLT":
        return wrap32(a << (b & 31))
    if op == "SRT":
        return wrap32((a & M32) >> (b & 31))
    if op == "SRA":
        return wrap32(a >> (b & 31))
    if op == "LAND":
        return wrap32(a & b)
    if op == "LOR":
        return wrap32(a | b)
    if op == "LXOR":
        return wrap32(a ^ b)
    if op == "LNAND":
        return wrap32(~(a & b))
    if op == "LNOR":
        return wrap32(~(a | b))
    if op == "LXNOR":
        return wrap32(~(a ^ b))
    if op in ("JUMP", "EXIT", "NOP"):
        return np.zeros_like(np.asarray(a, np.int64))
    raise ValueError(f"no semantics for {op}")


def run(kernel, mems: np.ndarray, frac_bits: int = 16,
        arithmetic: str = "int32") -> Tuple[Dict[int, np.ndarray], np.ndarray]:
    """(last-iteration value of every node {id: (B,)}, final memories
    (B, M)), all as int64 holding signed 32-bit words."""
    if arithmetic not in ("int32", "float32"):
        raise ValueError(f"unknown arithmetic {arithmetic!r}")
    mem = wrap32(np.asarray(mems, np.int64)).copy()
    batch, words = mem.shape
    rows = np.arange(batch)
    ops = {n.id: n.op for n in kernel.nodes}
    order = node_order(kernel)
    carried = {c.update: np.int64(c.init) for c in kernel.carries}
    vals: Dict[int, np.ndarray] = {}
    for _ in range(kernel.trip):
        vals = {}
        for nid in order:
            op = ops[nid]
            imm = np.int64(kernel.node_imm[nid])
            a, b = kernel.node_srcs[nid]
            fetched = []
            for operand, absent in ((a, 0 if op in ("LWI", "SWI") else imm),
                                    (b, imm)):
                if operand is None:
                    fetched.append(np.int64(absent))
                elif _is_value(operand):
                    fetched.append(vals[operand.node])
                elif _is_carry(operand):
                    fetched.append(carried[operand.update])
                else:
                    fetched.append(np.int64(operand))
            av, bv = fetched
            if op in ("LWI", "LWD", "SWI", "SWD"):
                addr = np.broadcast_to(
                    av + (imm if op in ("LWI", "SWI") else 0), (batch,))
                if (addr < 0).any() or (addr >= words).any():
                    raise IndexError(f"{kernel.name}: node {nid} ({op}) "
                                     f"address outside [0, {words})")
                if op in ("LWI", "LWD"):
                    out = mem[rows, addr]
                else:
                    out = np.broadcast_to(np.asarray(bv, np.int64), (batch,))
                    mem[rows, addr] = out
            elif op in ("BSFA", "BZFA"):
                flag = vals[kernel.flag_deps[nid]]
                hit = flag < 0 if op == "BSFA" else flag == 0
                out = np.where(hit, av, bv)
            else:
                out = _alu(op, av, bv, frac_bits, arithmetic)
            vals[nid] = np.broadcast_to(np.asarray(out, np.int64), (batch,))
        for c in kernel.carries:
            carried[c.update] = vals[c.update]
    return vals, mem
