"""The benchmark's vectorised traffic generator (bench/traffic.py)."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import traffic  # noqa: E402
from bench.traffic import STRATEGIES, KernelTraffic, Region  # noqa: E402

KERNEL_FILES = sorted((ROOT / "bench" / "kernels").glob("*.json"))
N, WORDS = 500, 128
SEED = 2 ** 31 + 12345


def _kernel(path: Path) -> KernelTraffic:
    return KernelTraffic.from_json(path.stem, json.loads(path.read_text()))


def _imm_and_extremes(region, clip):
    pool = {region.lo, region.hi - 1, 0, 1, -1, traffic.IMM_MIN,
            traffic.IMM_MAX, *traffic.OVERFLOW_WORDS}
    if clip:
        pool = {min(max(v, region.lo), region.hi - 1) for v in pool}
    return pool


@pytest.mark.parametrize("path", KERNEL_FILES, ids=lambda p: p.stem)
def test_regions_are_the_programs(path):
    """Each kernel file holds the regions and the clip the program's own
    corpus declares for that kernel."""
    from repro.cgra.registry import ensure_registered
    from repro.fuzz.corpus import kernel_regions, uses_wide_product

    ensure_registered()
    kt = _kernel(path)
    assert kt.regions == tuple(Region(r.base, r.length, r.lo, r.hi)
                               for r in kernel_regions(kt.name))
    assert kt.clip == uses_wide_product(kt.name)


@pytest.mark.parametrize("path", KERNEL_FILES, ids=lambda p: p.stem)
def test_strategies_stay_in_their_regions(path):
    kt = _kernel(path)
    job = 3
    mems = traffic.job_memories(kt, SEED, job, N, WORDS).astype(np.int64)
    assert mems.shape == (N, WORDS) and mems.dtype == np.int64
    index = job * N + np.arange(N)
    strategy = np.array(STRATEGIES)[index % len(STRATEGIES)]
    inside = np.zeros(WORDS, bool)
    for r in kt.regions:
        inside[r.base:r.base + r.length] = True
        block = mems[:, r.base:r.base + r.length]
        uniform = block[strategy == "uniform"]
        assert ((uniform >= r.lo) & (uniform < r.hi)).all()
        boundary = block[strategy == "boundary"]
        assert set(np.unique(boundary)) <= _imm_and_extremes(r, kt.clip)
        sparse = block[strategy == "sparse"]
        hot = sparse[sparse != 0]
        assert ((hot >= r.lo) & (hot < r.hi)).all()
        assert 0.05 < hot.size / sparse.size < 0.2
        fill_rows = strategy == "fill"
        fill = block[fill_rows]
        expect = np.where((index[fill_rows] // 5) % 2 == 0, 0, -1)
        assert (fill == expect[:, None]).all()
        overflow = block[strategy == "overflow"]
        assert ((overflow >= traffic.INT32_MIN)
                & (overflow <= traffic.INT32_MAX)).all()
        picked = np.isin(overflow, list(_imm_and_extremes(r, kt.clip)))
        full = ~picked.all(axis=1)
        assert full.any() and not full.all()   # half draw the full range
    assert (mems[:, ~inside] == 0).all()


def test_fxpmul_kernels_clip_every_strategy():
    """A kernel with FXPMUL (ema_fxp) never leaves its declared range."""
    from repro.cgra.registry import ensure_registered
    from repro.fuzz.corpus import kernel_regions, uses_wide_product

    ensure_registered()
    assert uses_wide_product("ema_fxp")
    kt = KernelTraffic("ema_fxp", tuple(
        Region(r.base, r.length, r.lo, r.hi)
        for r in kernel_regions("ema_fxp")), True)
    mems = traffic.job_memories(kt, SEED, 0, N, WORDS)
    for r in kt.regions:
        block = mems[:, r.base:r.base + r.length]
        assert ((block >= r.lo) & (block <= r.hi - 1)).all()
        assert block.min() == r.lo and block.max() == r.hi - 1
    unclipped = KernelTraffic("ema_fxp", kt.regions, False)
    wide = traffic.job_memories(unclipped, SEED, 0, N, WORDS)
    assert wide.min() == traffic.INT32_MIN


def test_jobs_are_seeded_and_distinct():
    kt = _kernel(KERNEL_FILES[0])
    a = traffic.job_memories(kt, SEED, 7, 64, WORDS)
    assert np.array_equal(a, traffic.job_memories(kt, SEED, 7, 64, WORDS))
    assert not np.array_equal(a, traffic.job_memories(kt, SEED, 8, 64, WORDS))
    assert not np.array_equal(
        a, traffic.job_memories(kt, SEED + 1, 7, 64, WORDS))
