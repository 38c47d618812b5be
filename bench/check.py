"""The comparison that decides ``correct``.

For every memory of every job in the window it compares what the timed
path produced at the execution seam, and the verdict ``fuzz_program``
returned, with :mod:`bench.reference`:

* ``memories_unchecked``  memories of the window with no captured seam
  output (the seam was bypassed or returned another number of rows);
* ``mem_words_wrong``     final data-memory words that differ;
* ``node_values_wrong``   last-iteration node values that differ, over
  the nodes the schedule exposes in its last iteration;
* ``verdicts_wrong``      memories whose verdict (mismatch or not) is not
  the one the reference gives to the seam's output.

Each is exact: its limit is 0 (readings in ``PERF.md``).  Words and
values are compared as 32-bit patterns.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

from . import reference

M32 = reference.M32
LIMITS = {"memories_unchecked": 0, "mem_words_wrong": 0,
          "node_values_wrong": 0, "verdicts_wrong": 0}
BLOCK = 16384        # memories per reference call


def _stack_job(job) -> Optional[tuple]:
    """(final memories (n, M), {node: (n,)}) of one job, or None when the
    captured chunks do not cover its memories."""
    if not job.chunks:
        return None
    mems = np.concatenate([np.asarray(c.final_mem) for c in job.chunks])
    nodes = sorted(set.intersection(
        *(set(c.node_values) for c in job.chunks)))
    if mems.shape != job.memories.shape or not nodes:
        return None
    return mems, {n: np.concatenate([c.node_values[n] for c in job.chunks])
                  for n in nodes}


def reference_outputs(program, inputs: np.ndarray, frac_bits: int,
                      arithmetic: str = "int32"):
    """The reference over ``inputs`` in blocks of :data:`BLOCK` rows."""
    vals: Dict[int, List[np.ndarray]] = defaultdict(list)
    mems = []
    for lo in range(0, len(inputs), BLOCK):
        v, m = reference.run(program, inputs[lo:lo + BLOCK], frac_bits,
                             arithmetic)
        for n, x in v.items():
            vals[n].append(x)
        mems.append(m)
    return {n: np.concatenate(x) for n, x in vals.items()}, np.concatenate(mems)


def compare(jobs, programs: Dict[str, object], frac_bits: int,
            stand_in: Optional[str] = None) -> Dict[str, int]:
    """Counts of :data:`LIMITS` over ``jobs``.

    ``stand_in="float32"`` is the control: the reference in float32 put in
    the seam's place, judged against the int32 reference with the verdicts
    the program returned.
    """
    counts = dict.fromkeys(LIMITS, 0)
    by_kernel = defaultdict(list)
    for job in jobs:
        seam = _stack_job(job)
        if seam is None:
            counts["memories_unchecked"] += len(job.memories)
        else:
            by_kernel[job.kernel].append((job, seam))
    for kernel, entries in by_kernel.items():
        inputs = np.concatenate([job.memories for job, _ in entries])
        want_vals, want_mem = reference_outputs(programs[kernel], inputs,
                                                frac_bits)
        if stand_in is None:
            got_mem = np.concatenate([s[0] for _, s in entries])
            shared = sorted(set.intersection(
                *(set(s[1]) for _, s in entries)) & set(want_vals))
            got_vals = {n: np.concatenate([s[1][n] for _, s in entries])
                        for n in shared}
        else:
            got_vals, got_mem = reference_outputs(
                programs[kernel], inputs, frac_bits, stand_in)
            shared = sorted(set(entries[0][1][1]) & set(got_vals))
        bad_words = ((np.asarray(got_mem, np.int64) & M32)
                     != (want_mem & M32))
        wrong = bad_words.any(axis=1)
        counts["mem_words_wrong"] += int(bad_words.sum())
        for n in shared:
            bad = ((np.asarray(got_vals[n], np.int64) & M32)
                   != (want_vals[n] & M32))
            counts["node_values_wrong"] += int(bad.sum())
            wrong |= bad
        flagged = np.zeros(len(inputs), bool)
        lo = 0
        for job, _ in entries:
            flagged[lo + np.asarray(job.failing, int)] = True
            lo += len(job.memories)
        counts["verdicts_wrong"] += int((flagged != wrong).sum())
    return counts


def correct(counts: Dict[str, int]) -> bool:
    return all(counts[k] <= limit for k, limit in LIMITS.items())


def lines(counts: Dict[str, int]) -> List[str]:
    return [f"{k} {counts[k]} limit {limit}" for k, limit in LIMITS.items()]
