"""One run of one cell: set-up, the measured window, the check.

Set-up maps each kernel of the cell through ``Toolchain`` (with the
program's mapping cache at ``bench/.cache/mappings``), starts each
kernel's stream of memories at the seed, and warms up every shape the
window uses with one chunk of each kernel.  The window then calls
``repro.fuzz.engine.fuzz_program`` as ``repro fuzz`` does (no backend
argument, activity on), one job after another, kernels taking turns, until
``seconds`` have passed; it closes at the end of the round of jobs (one of
each kernel) that crosses the deadline, so every window holds the same
mix.  Each job gets memories that no job of the run had before, drawn
from the seed just before the job starts.  A job's span runs from the
call of ``fuzz_program`` to its return, and the window's length is the sum
of its jobs' spans: drawing the memories lies between them and is the
benchmark's work, not the system's, so a faster program never has its
traffic drawn inside the measured time.  The check (:mod:`bench.check`)
runs after the window.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from . import check, traffic, xplane
from .seam import Seam, TARGETS
from .spec import BENCH, Cell

MAPPING_CACHE = BENCH / ".cache" / "mappings"
TRACE_DIR = BENCH / ".cache" / "trace"
WARM_UP_JOB = 2 ** 40          # job index of the warm-up stream
TRACE_SECONDS = 5.0            # a traced run traces the window's start


@dataclass
class Job:
    kernel: str
    memories: np.ndarray
    start: float
    end: float
    failing: List[int]
    chunks: list = field(repr=False, default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class Kernel:
    name: str
    program: object                 # the kernel's LoopBuilder
    mapping: object
    arch: str
    traffic: traffic.KernelTraffic
    seed: int = 0
    jobs: int = 0               # jobs of the run so far


@dataclass
class Record:
    """What the metric readers read (``bench/metrics/<name>.py``)."""

    setup_s: float
    window_s: float                       # the sum of the jobs' spans
    jobs: List[Job]
    layer_s: Dict[str, Optional[float]]   # host seconds per layer, traced
    pe_cycles: int                        # simulated in the traced part
    trace: Optional[xplane.Reduced]       # the traced part's reduction

    @property
    def memories(self) -> int:
        return sum(len(j.memories) for j in self.jobs)


def map_kernels(cell: Cell) -> Dict[str, Kernel]:
    """Each kernel of the cell, mapped with the configuration's budget."""
    from repro.cgra.registry import ensure_registered
    from repro.core.mapper import MapperConfig
    from repro.toolchain.session import Toolchain

    ensure_registered()
    budget = cell.config["mapper"]
    tc = Toolchain(cell.config["arch"],
                   MapperConfig(per_ii_timeout_s=budget["per_ii_timeout_s"],
                                total_timeout_s=budget["total_timeout_s"],
                                ii_max=budget["ii_max"]),
                   cache=str(MAPPING_CACHE))
    if tc.grid.num_pes != cell.config["pes"]:
        raise ValueError(f"{cell.config['arch']} has {tc.grid.num_pes} PEs, "
                         f"the configuration states {cell.config['pes']}")
    arch = tc.arch or f"{tc.grid.spec.rows}x{tc.grid.spec.cols}"
    kernels = {}
    for name, kt in cell.kernels.items():
        prog = tc.program(name)
        res = tc.map(prog)
        if res.mapping is None:
            raise RuntimeError(f"{name} did not map on {arch}: {res.status}")
        kernels[name] = Kernel(name, prog.builder, res.mapping, arch, kt)
    return kernels


def start_traffic(kernels: Dict[str, Kernel], seed: int) -> None:
    """Start a run's traffic: each kernel's stream from ``seed``, at its
    first job."""
    for k in kernels.values():
        k.seed, k.jobs = seed, 0


def next_memories(kernel: Kernel, cell: Cell) -> np.ndarray:
    """The memories of the kernel's next job."""
    mems = traffic.job_memories(kernel.traffic, kernel.seed, kernel.jobs,
                                cell.workload["job_memories"],
                                cell.config["memory_words"])
    kernel.jobs += 1
    return mems


def fuzz(kernel: Kernel, mems: np.ndarray, batch: int):
    """One job, called as ``repro fuzz`` calls it."""
    from repro.fuzz.engine import fuzz_program

    return fuzz_program(kernel.program, kernel.mapping, mems, batch=batch,
                        kernel=kernel.name, arch=kernel.arch)


def warm_up(kernels: Dict[str, Kernel], cell: Cell) -> None:
    """Every chunk shape the window runs, once for each kernel: a whole
    batch and, where a job does not divide into batches, its last chunk."""
    n, batch = cell.workload["job_memories"], cell.workload["batch"]
    sizes = {min(n, batch)} | ({n % batch} if n > batch and n % batch else set())
    for kernel in kernels.values():
        for size in sorted(sizes):
            mems = traffic.job_memories(kernel.traffic, kernel.seed,
                                        WARM_UP_JOB, size,
                                        cell.config["memory_words"])
            fuzz(kernel, mems, batch)


def window(kernels: Dict[str, Kernel], cell: Cell, deadline: float,
           seam: Seam, jobs: List[Job]) -> None:
    """Append jobs to ``jobs``, kernels taking turns, until a round (one
    job of each kernel) ends at or after ``deadline``."""
    order = list(kernels.values())
    batch = cell.workload["batch"]
    while True:
        kernel = order[len(jobs) % len(order)]
        mems = next_memories(kernel, cell)
        seam.last_iteration = kernel.program.trip - 1
        seam.chunks = []
        start = time.monotonic()
        with seam.annotate("job"):
            rep = fuzz(kernel, mems, batch)
        end = time.monotonic()
        jobs.append(Job(kernel.name, mems, start, end, list(rep.failing),
                        seam.chunks))
        if end >= deadline and len(jobs) % len(order) == 0:
            return


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def _start_trace() -> str:
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    return str(TRACE_DIR)


def _reduce_trace(trace_dir: str) -> Optional[xplane.Reduced]:
    import jax

    t0 = time.monotonic()
    jax.profiler.stop_trace()
    t1 = time.monotonic()
    labels = {span for _, _, span in TARGETS} | {"job"}
    reduced = xplane.reduce(xplane.load(trace_dir), labels)
    shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"trace: stop {t1 - t0:.1f} s, read and reduce "
        f"{time.monotonic() - t1:.1f} s")
    return reduced


def log(message: str) -> None:
    print(f"bench: {message}", file=sys.stderr, flush=True)


@dataclass
class Measured:
    jobs: List[Job]
    layer_s: Dict[str, Optional[float]]
    pe_cycles: int
    trace: Optional[xplane.Reduced]


def measure(kernels: Dict[str, Kernel], cell: Cell, seconds: float,
            traced: bool) -> Measured:
    """The window: jobs until ``seconds`` have passed.  A traced run traces
    the jobs of its first :data:`TRACE_SECONDS`."""
    jobs: List[Job] = []
    reduced, traced_cycles = None, 0
    with Seam(spans=traced) as seam:
        t0 = time.monotonic()
        if traced:
            trace_dir = _start_trace()
            with seam.annotate("window"):
                window(kernels, cell, t0 + min(seconds, TRACE_SECONDS), seam,
                       jobs)
            traced_cycles = seam.pe_cycles
            reduced = _reduce_trace(trace_dir)
        if time.monotonic() < t0 + seconds:
            window(kernels, cell, t0 + seconds, seam, jobs)
    return Measured(jobs, seam.layer_seconds(), traced_cycles, reduced)


def compare(kernels: Dict[str, Kernel], cell: Cell, jobs: List[Job],
            stand_in: Optional[str] = None) -> Dict[str, int]:
    """The check's counts over ``jobs`` (see :mod:`bench.check`)."""
    for job in jobs:            # let go of the device buffers
        for chunk in job.chunks:
            chunk.final_mem = np.asarray(chunk.final_mem)
    programs = {k.name: k.program for k in kernels.values()}
    return check.compare(jobs, programs, cell.config["fxpmul_frac_bits"],
                         stand_in)


@dataclass
class Outcome:
    result: dict                      # the result line
    compiles: dict                    # set-up and window compile counts
    checks: List[str]                 # each compared number and its limit


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, device: dict, counter) -> Outcome:
    """One run of ``cell``.  ``t_start`` is the process's start on the
    monotonic clock; ``counter`` a :class:`bench.compiles.CompileCounter`
    made at that start."""
    t_map = time.monotonic()
    kernels = map_kernels(cell)
    start_traffic(kernels, seed)
    t_warm = time.monotonic()
    warm_up(kernels, cell)
    gc.collect()
    setup_s = time.monotonic() - t_start
    log(f"set-up {setup_s:.2f} s: start {t_map - t_start:.2f} s, map "
        f"{t_warm - t_map:.2f} s, warm-up "
        f"{time.monotonic() - t_warm:.2f} s")
    compiles = {"setup": counter.since((0, 0.0, 0))}
    mark = counter.mark()
    m = measure(kernels, cell, seconds, traced)
    compiles["window"] = counter.since(mark)
    jobs = m.jobs
    window_s = sum(job.seconds for job in jobs)
    log(f"window: {len(jobs)} jobs, {window_s:.2f} s in jobs, "
        f"{jobs[-1].end - jobs[0].start:.2f} s from first start to last end")
    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    record = Record(setup_s, window_s, jobs, m.layer_s, m.pe_cycles, m.trace)
    metrics = {}
    for metric in (cell.per_layer if traced else cell.end_to_end):
        value = metric.read(record)
        if value is not None:
            metrics[metric.name] = {"value": value, "unit": metric.unit}
    counts = compare(kernels, cell, jobs)
    result = {"correct": check.correct(counts),
              "attempted": record.memories,
              "failed": sum(len(j.failing) for j in jobs),
              "metrics": metrics, "device": device}
    if m.trace is not None:
        device.update(busy_s=m.trace.busy_ns / 1e9,
                      window_s=m.trace.window_ns / 1e9)
        result["breakdown"] = {"device_ops": m.trace.device_ops,
                               "idle_gaps": m.trace.idle_gaps}
    result["checks"] = {k: {"value": counts[k], "limit": limit}
                        for k, limit in check.LIMITS.items()}
    return Outcome(result, compiles, check.lines(counts))
