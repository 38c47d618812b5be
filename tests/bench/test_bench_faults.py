"""The benchmark's check on the CPU at a small size: a sound run is
correct; the control (the reference in float32) and each fault planted
underneath the timed path are not.

The faults are those a one-chip cell can have: a step that returns its
state unchanged, half of each batch left out (its answers copied from the
other half), an answer altered where it is produced, a verdict altered,
and the seam bypassed.  No cell runs across chips, so there is no
exchange between chips to leave out.
"""
import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, harness, spec  # noqa: E402
from bench.traffic import KernelTraffic  # noqa: E402

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 4242
SECONDS = 0.3


@pytest.fixture(scope="module")
def cell():
    """``torus4x4.fleet`` cut to two quick kernels (dotprod, saxpy) and
    16-memory jobs; saxpy stores, dotprod only loads."""
    base = spec.load_cell("torus4x4.fleet")
    kernels = ("dotprod", "saxpy")
    workload = dict(base.workload, kernels=list(kernels), job_memories=16,
                    batch=16)
    traffic = {k: KernelTraffic.from_json(k, spec.load_json(
        spec.BENCH / "kernels" / f"{k}.json")) for k in kernels}
    points = spec.load_cell("torus6x6.points")
    return dataclasses.replace(base, workload=workload, kernels=traffic,
                               end_to_end=points.end_to_end)


@pytest.fixture(scope="module")
def counter():
    from bench.compiles import CompileCounter

    return CompileCounter()


@pytest.fixture(autouse=True)
def mapping_cache(tmp_path_factory, monkeypatch):
    root = tmp_path_factory.getbasetemp() / "bench-mappings"
    monkeypatch.setattr(harness, "MAPPING_CACHE", root)


def _run(cell, counter):
    return harness.run_cell(cell, SEED, SECONDS, False, time.monotonic(),
                            DEVICE, counter)


def test_sound_run_is_correct(cell, counter):
    out = _run(cell, counter)
    assert out.result["correct"], out.checks
    assert out.result["attempted"] >= 32 and out.result["failed"] == 0
    assert list(out.result)[-1] == "checks"
    assert set(out.result["metrics"]) == {"verified_mem_per_s",
                                          "verdict_p95_ms", "setup_s"}
    assert out.compiles["window"]["compiles"] == 0


def test_control_is_not_correct(cell):
    kernels = harness.map_kernels(cell)
    harness.start_traffic(kernels, SEED)
    jobs = harness.measure(kernels, cell, SECONDS, False).jobs
    assert check.correct(harness.compare(kernels, cell, jobs))
    control = harness.compare(kernels, cell, jobs, "float32")
    assert not check.correct(control)
    assert control["mem_words_wrong"] > 0 and control["verdicts_wrong"] > 0


def _unchanged(real):
    from repro.cgra.simulator import preset_state

    def execute_asm(asm, grid, mem, batch=1, backend="ref"):
        final, outs, out0 = real(asm, grid, mem, batch=batch,
                                 backend=backend)
        state = preset_state(asm, grid.num_pes, mem, batch)
        return state, np.broadcast_to(out0, outs.shape).copy(), out0
    return execute_asm


def _half_batch(real):
    def execute_asm(asm, grid, mem, batch=1, backend="ref"):
        half = batch // 2
        final, outs, out0 = real(asm, grid, mem[:half], batch=half,
                                 backend=backend)
        mem_out = np.asarray(final.mem)
        rows = np.arange(batch) % half
        final = final._replace(mem=mem_out[rows])
        _, _, out0 = real(asm, grid, mem, batch=batch, backend=backend)
        return final, np.asarray(outs)[:, rows], out0
    return execute_asm


def _altered(real):
    def execute_asm(asm, grid, mem, batch=1, backend="ref"):
        final, outs, out0 = real(asm, grid, mem, batch=batch,
                                 backend=backend)
        changed = np.array(final.mem)
        changed[0, 70] ^= 1 << 3
        return final._replace(mem=changed), outs, out0
    return execute_asm


@pytest.mark.parametrize("fault,count", [
    (_unchanged, "node_values_wrong"),
    (_half_batch, "mem_words_wrong"),
    (_altered, "mem_words_wrong"),
])
def test_seam_faults_are_caught(cell, counter, monkeypatch, fault, count):
    import repro.cgra.simulator as simulator

    monkeypatch.setattr(simulator, "execute_asm",
                        fault(simulator.execute_asm))
    out = _run(cell, counter)
    assert not out.result["correct"]
    assert out.result["checks"][count]["value"] > 0


def test_altered_verdict_is_caught(cell, counter, monkeypatch):
    import repro.fuzz.engine as engine

    real = engine.compare_batch

    def compare_batch(*args):
        bad = real(*args)
        bad[0] = True
        return bad
    monkeypatch.setattr(engine, "compare_batch", compare_batch)
    out = _run(cell, counter)
    assert not out.result["correct"]
    assert out.result["checks"]["verdicts_wrong"]["value"] > 0


def test_bypassed_seam_is_caught(cell, counter, monkeypatch):
    import repro.fuzz.engine as engine

    def fuzz_program(program, mapping, mems, batch=1024, **kwargs):
        return engine.FuzzReport(kernel=program.name, arch="4x4",
                                 status="ok", memories=len(mems))
    monkeypatch.setattr(engine, "fuzz_program", fuzz_program)
    out = _run(cell, counter)
    assert not out.result["correct"]
    assert out.result["checks"]["memories_unchecked"]["value"] == \
        out.result["attempted"]
