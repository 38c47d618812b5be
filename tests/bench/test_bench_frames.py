"""The GSM-frame cell (``torus4x4-m512.frames``) at its own memory size:
its configuration and kernel file agree with the program's registry and
corpus, its traffic fills 512-word memories, the reference agrees with
the program's oracle there, and the float32 control fails it."""
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference, spec, traffic  # noqa: E402
from bench.traffic import STRATEGIES, Region  # noqa: E402

CELL = "torus4x4-m512.frames"
KERNEL = "gsm_frame"
SEED = 2 ** 31 + 2024
N = 200


def _registry():
    from repro.cgra.registry import ensure_registered, get_kernel

    ensure_registered()
    return get_kernel(KERNEL)


def _cell():
    return spec.load_cell(CELL)


def _memories(n: int = N) -> np.ndarray:
    cell = _cell()
    return traffic.job_memories(cell.kernels[KERNEL], SEED, 0, n,
                                cell.config["memory_words"])


def test_config_and_kernel_file_are_the_programs():
    from repro.fuzz.corpus import kernel_regions, uses_wide_product

    cell = _cell()
    assert cell.workload["kernels"] == [KERNEL]
    assert cell.config["memory_words"] == _registry().mem_words == 512
    doc = json.loads((ROOT / "bench" / "kernels" / f"{KERNEL}.json")
                     .read_text())
    assert [tuple(r) for r in doc["regions"]] == [
        (r.base, r.length, r.lo, r.hi) for r in kernel_regions(KERNEL)]
    assert doc["clip"] == uses_wide_product(KERNEL) is False
    base = json.loads((ROOT / "bench" / "configs" / "torus4x4.json")
                      .read_text())
    same = ("arch", "rows", "cols", "pes", "topology", "registers_per_pe",
            "word_bits", "arithmetic", "fxpmul_frac_bits", "guarantees",
            "mapper")
    assert {k: cell.config[k] for k in same} == {k: base[k] for k in same}
    assert cell.config["reduced"] == []


def test_traffic_fills_512_word_memories():
    cell = _cell()
    kt = cell.kernels[KERNEL]
    mems = _memories().astype(np.int64)
    assert mems.shape == (N, 512)
    strategy = np.array(STRATEGIES)[np.arange(N) % len(STRATEGIES)]
    inside = np.zeros(512, bool)
    for r in kt.regions:
        assert r == Region(r.base, 160, -(2 ** 14), 2 ** 14)
        inside[r.base:r.base + r.length] = True
        block = mems[:, r.base:r.base + r.length]
        uniform = block[strategy == "uniform"]
        assert ((uniform >= r.lo) & (uniform < r.hi)).all()
        overflow = block[strategy == "overflow"]
        assert (overflow < r.lo).any() or (overflow >= r.hi).any()
    assert (mems[:, ~inside] == 0).all()
    assert (mems[:, inside] != 0).any(axis=1).sum() > N // 2


def test_reference_matches_batched_oracle_at_512_words():
    from repro.fuzz.engine import batched_oracle

    program = _registry().factory()
    mems = _memories()
    vals, final = reference.run(program, mems)
    want_vals, want_final = batched_oracle(program, mems)
    assert set(want_vals) == set(vals)
    for n, want in want_vals.items():
        got = vals[n] & reference.M32
        assert np.array_equal(got, np.broadcast_to(want, got.shape)
                              & reference.M32), n
    assert np.array_equal(final & reference.M32, want_final & reference.M32)


def test_float32_control_fails_the_cell():
    program = _registry().factory()
    mems = _memories()
    vals, final = reference.run(program, mems)
    cvals, cfinal = reference.run(program, mems, arithmetic="float32")
    wrong = int((cfinal != final).sum())
    wrong += sum(int((cvals[n] != vals[n]).sum()) for n in vals)
    assert wrong > 0
