"""The benchmark's plain reference (bench/reference.py) against the
program's batched oracle, and its float32 control."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference, traffic  # noqa: E402
from bench.traffic import KernelTraffic  # noqa: E402

WORKLOADS = {p.stem: json.loads(p.read_text())
             for p in sorted((ROOT / "bench" / "workloads").glob("*.json"))}
CELL_KERNELS = sorted({k for w in WORKLOADS.values() for k in w["kernels"]})
SEED = 2 ** 31 + 777


def _memories(kernel: str, n: int = 400) -> np.ndarray:
    doc = json.loads((ROOT / "bench" / "kernels" / f"{kernel}.json")
                     .read_text())
    return traffic.job_memories(KernelTraffic.from_json(kernel, doc), SEED,
                                0, n, 128)


def _program(kernel: str):
    from repro.cgra.registry import ensure_registered, kernel_program

    ensure_registered()
    return kernel_program(kernel)


def test_reference_imports_nothing_of_the_program():
    source = (ROOT / "bench" / "reference.py").read_text()
    assert "repro" not in source.split('"""', 2)[2]


@pytest.mark.parametrize("kernel", CELL_KERNELS)
def test_reference_matches_batched_oracle(kernel):
    from repro.fuzz.engine import batched_oracle

    program, mems = _program(kernel), _memories(kernel)
    vals, final = reference.run(program, mems)
    want_vals, want_final = batched_oracle(program, mems)
    assert set(want_vals) == set(vals)
    for n, want in want_vals.items():
        got = vals[n] & reference.M32
        assert np.array_equal(got, np.broadcast_to(want, got.shape)
                              & reference.M32), n
    assert np.array_equal(final & reference.M32, want_final & reference.M32)


@pytest.mark.parametrize("cell", sorted(WORKLOADS))
def test_float32_control_fails_every_cell(cell):
    """The control (the reference in float32) differs from the int32
    reference on some kernel of every cell."""
    wrong = 0
    for kernel in WORKLOADS[cell]["kernels"]:
        program, mems = _program(kernel), _memories(kernel)
        vals, final = reference.run(program, mems)
        cvals, cfinal = reference.run(program, mems, arithmetic="float32")
        wrong += int((cfinal != final).sum())
        wrong += sum(int((cvals[n] != vals[n]).sum()) for n in vals)
    assert wrong > 0


def test_wrap_and_fxpmul():
    assert reference.wrap32(np.int64(2 ** 31)) == -(2 ** 31)
    assert reference.wrap32(np.int64(-(2 ** 31) - 1)) == 2 ** 31 - 1
    a, b = np.array([3 << 16, -(5 << 16)]), np.array([7 << 16, 3 << 16])
    got = reference._alu("FXPMUL", a, b, 16, "int32")
    assert list(got) == [21 << 16, -(15 << 16)]
