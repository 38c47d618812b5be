"""Vectorised memory traffic: the benchmark's copy of ``make_corpus``.

A kernel's traffic is a list of input regions (``base``, ``length``,
``lo``, ``hi``) and a ``clip`` flag, read from ``bench/kernels/<name>.json``.
Memory *i* of a kernel's stream uses ``STRATEGIES[i % 5]``, as the
program's corpus does:

* ``uniform``  every region word uniform in ``[lo, hi)``;
* ``boundary`` drawn from the region bounds, 0, +-1 and the 16-bit
  immediate extremes;
* ``sparse``   zero, with one word in eight uniform in ``[lo, hi)``;
* ``fill``     all zeros or all ones (-1), alternating every five memories;
* ``overflow`` int32 extremes and alternating-bit words, or, for half of
  the (memory, region) pairs, words uniform over the whole int32 range.

With ``clip`` (kernels that hold FXPMUL) every extreme is clipped into the
region's ``[lo, hi - 1]`` and ``overflow`` never draws outside it.  Words
outside every region stay zero.

Job ``j`` of a kernel holds memories ``j*n .. j*n + n - 1`` of the stream,
drawn from a generator seeded by ``(seed, kernel, j)`` alone, so a job's
memories do not depend on how many jobs were made before it.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Sequence, Tuple

import numpy as np

STRATEGIES: Tuple[str, ...] = (
    "uniform", "boundary", "sparse", "fill", "overflow")
INT32_MIN = -(1 << 31)
INT32_MAX = (1 << 31) - 1
IMM_MIN, IMM_MAX = -(1 << 15), (1 << 15) - 1
OVERFLOW_WORDS = (INT32_MIN, INT32_MAX, INT32_MIN + 1, 0x55555555,
                  -0x55555556)
SPARSE_SHARE = 0.125


@dataclass(frozen=True)
class Region:
    base: int
    length: int
    lo: int
    hi: int


@dataclass(frozen=True)
class KernelTraffic:
    """What one kernel reads: its regions and whether extremes clip."""

    name: str
    regions: Tuple[Region, ...]
    clip: bool

    @classmethod
    def from_json(cls, name: str, doc: dict) -> "KernelTraffic":
        return cls(name, tuple(Region(*r) for r in doc["regions"]),
                   bool(doc["clip"]))


def _pool(region: Region, clip: bool, extremes: Sequence[int]) -> np.ndarray:
    vals = {region.lo, region.hi - 1, 0, 1, -1, *extremes}
    if clip:
        vals = {min(max(v, region.lo), region.hi - 1) for v in vals}
    return np.array(sorted(vals), np.int64)


def _rng(seed: int, kernel: str, job: int) -> np.random.Generator:
    words = [seed & (2 ** 64 - 1), zlib.crc32(kernel.encode()), job]
    return np.random.default_rng(np.random.SeedSequence(words))


def job_memories(traffic: KernelTraffic, seed: int, job: int, n: int,
                 mem_words: int) -> np.ndarray:
    """(n, mem_words) int32 memories of job ``job`` of a kernel's stream."""
    rng = _rng(seed, traffic.name, job)
    index = job * n + np.arange(n)
    strategy = index % len(STRATEGIES)
    mem = np.zeros((n, mem_words), np.int64)
    for region in traffic.regions:
        cols = slice(region.base, region.base + region.length)
        shape = lambda rows: (len(rows), region.length)  # noqa: E731
        lo, hi = region.lo, region.hi

        rows = np.nonzero(strategy == 0)[0]
        mem[rows, cols] = rng.integers(lo, hi, shape(rows))

        rows = np.nonzero(strategy == 1)[0]
        pool = _pool(region, traffic.clip, (IMM_MIN, IMM_MAX))
        mem[rows, cols] = rng.choice(pool, shape(rows))

        rows = np.nonzero(strategy == 2)[0]
        hot = rng.random(shape(rows)) < SPARSE_SHARE
        mem[rows, cols] = np.where(hot, rng.integers(lo, hi, shape(rows)), 0)

        rows = np.nonzero(strategy == 3)[0]
        word = np.where((index[rows] // len(STRATEGIES)) % 2 == 0, 0, -1)
        if traffic.clip:
            word = np.clip(word, lo, hi - 1)
        mem[rows, cols] = word[:, None]

        rows = np.nonzero(strategy == 4)[0]
        pool = _pool(region, traffic.clip, OVERFLOW_WORDS)
        picked = rng.choice(pool, shape(rows))
        if not traffic.clip:
            wide = rng.random(len(rows)) >= 0.5
            full = rng.integers(INT32_MIN, INT32_MAX, shape(rows),
                                endpoint=True)
            picked = np.where(wide[:, None], full, picked)
        mem[rows, cols] = picked
    return mem.astype(np.int32)
