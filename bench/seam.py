"""Wrappers the benchmark places around the program's module attributes
for the measured window, and takes away after it.

Every run captures, at the execution seam
(``repro.cgra.simulator.execute_asm``), what the timed path produced for
each chunk: the final data memory and the last-iteration node values read
from the OUT trace.  The check compares them with the reference once the
window has closed.  The capture asks three things of the program: that
``fuzz_program`` runs every chunk through ``execute_asm``; that
``execute_asm`` returns ``(final, outs, out0)``, with ``final.mem`` the
chunk's final memories and ``outs`` its (rows, memories, PEs) OUT trace;
and that the assembled program's ``node_of_cell`` maps an OUT cell
(row, PE) to the (node, iteration) it holds.  Where a change to the
program drops one of them, the window's memories read as unchecked and
``correct`` as false until the capture is moved with it.

A traced run also times each layer on the host clock and writes a
``jax.profiler.TraceAnnotation`` for it, so that host spans and device
operations share the profiler's clock:

=================  ==========================================  =========
span               program attribute                           layer
=================  ==========================================  =========
seam               ``repro.cgra.simulator.execute_asm``         seam
seam.decode        ``repro.kernels.ops.decode_fields``          (in seam)
seam.preset        ``repro.cgra.simulator.preset_state``        (in seam)
seam.dispatch      ``repro.kernels.ops.run_program``            (in seam)
oracle             ``repro.fuzz.engine.batched_oracle``         oracle
harvest.compare    ``repro.fuzz.engine.compare_batch``          harvest
harvest.nodes      ``repro.fuzz.engine.node_values_from_outs``  harvest
harvest.activity   ``repro.fuzz.activity.ActivityAccumulator.update``  harvest
=================  ==========================================  =========

The harness adds ``window`` and ``job`` spans around the window and each
call of ``fuzz_program``.
"""
from __future__ import annotations

import contextlib
import functools
import importlib
import time
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

# (module, attribute path, span)
TARGETS = (
    ("repro.cgra.simulator", "execute_asm", "seam"),
    ("repro.kernels.ops", "decode_fields", "seam.decode"),
    ("repro.cgra.simulator", "preset_state", "seam.preset"),
    ("repro.kernels.ops", "run_program", "seam.dispatch"),
    ("repro.fuzz.engine", "batched_oracle", "oracle"),
    ("repro.fuzz.engine", "compare_batch", "harvest.compare"),
    ("repro.fuzz.engine", "node_values_from_outs", "harvest.nodes"),
    ("repro.fuzz.activity", "ActivityAccumulator.update", "harvest.activity"),
)
# the spans whose host time makes up each layer (seam.* lie inside seam)
LAYERS = {"seam": ("seam",), "oracle": ("oracle",),
          "harvest": ("harvest.compare", "harvest.nodes", "harvest.activity")}


class Chunk:
    """What the seam returned for one chunk of a job."""

    __slots__ = ("final_mem", "node_values")

    def __init__(self, final_mem, node_values: Dict[int, np.ndarray]):
        self.final_mem = final_mem
        self.node_values = node_values


def _owner(module: str, path: str):
    owner = importlib.import_module(module)
    *parents, attr = path.split(".")
    for name in parents:
        owner = getattr(owner, name)
    return owner, attr


class Seam:
    """Context manager: install the wrappers, restore the originals.

    ``chunks`` collects the captured chunks of the current job; the
    harness sets ``last_iteration`` (the job kernel's trip - 1) before
    each job and takes ``chunks`` after it.  With ``spans``, ``seconds``
    sums each span's host time and ``pe_cycles`` the simulated PE-cycles
    (rows x memories x PEs) of every chunk.
    """

    def __init__(self, spans: bool):
        self.spans = spans
        self.last_iteration = 0
        self.chunks: List[Chunk] = []
        self.seconds: Dict[str, float] = defaultdict(float)
        self.pe_cycles = 0
        self._saved = []

    def __enter__(self) -> "Seam":
        for module, path, span in TARGETS:
            if not self.spans and span != "seam":
                continue
            owner, attr = _owner(module, path)
            original = getattr(owner, attr)
            self._saved.append((owner, attr, original))
            wrapped = self._timed(original, span) if self.spans else original
            if span == "seam":
                wrapped = self._capturing(wrapped)
            setattr(owner, attr, wrapped)
        return self

    def __exit__(self, *exc) -> None:
        for owner, attr, original in reversed(self._saved):
            setattr(owner, attr, original)
        self._saved.clear()

    def annotate(self, span: str):
        """A profiler annotation in a traced run, else a no-op."""
        if not self.spans:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(span)

    def _timed(self, fn, span: str):
        import jax

        seconds = self.seconds

        @functools.wraps(fn)
        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(span):
                try:
                    return fn(*args, **kwargs)
                finally:
                    seconds[span] += time.perf_counter() - t0
        return timed

    def _capturing(self, execute_asm):
        @functools.wraps(execute_asm)
        def capturing(asm, *args, **kwargs):
            final, outs, out0 = execute_asm(asm, *args, **kwargs)
            last = self.last_iteration
            nodes = {n: np.array(outs[t, :, pe])
                     for (t, pe), (n, j) in asm.node_of_cell.items()
                     if j == last}
            self.chunks.append(Chunk(final.mem, nodes))
            self.pe_cycles += int(np.prod(np.shape(outs)))
            return final, outs, out0
        return capturing

    def layer_seconds(self) -> Dict[str, Optional[float]]:
        """Host seconds per layer (``None`` when no span was recorded)."""
        out: Dict[str, Optional[float]] = {}
        for layer, spans in LAYERS.items():
            timed = [self.seconds[s] for s in spans if s in self.seconds]
            out[layer] = sum(timed) if timed else None
        return out
