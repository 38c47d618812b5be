"""repro.fuzz: corpus determinism, batched-oracle equivalence, shrinker
minimality, fault-injection detection, activity/energy consistency, and
the mesh-seam neighbor-table contract.

The numpy-only half (corpus, batched oracle, batched body reference,
shrinker, neighbor tables, energy scaling) runs everywhere; everything
executing a bitstream on the PE array is jax-gated per test.
"""
import json

import numpy as np
import pytest

from repro.cgra.registry import ensure_registered, kernel_names, kernel_program
from repro.core.mapper import MapperConfig
from repro.frontend.ir import M32
from repro.fuzz.corpus import (
    STRATEGIES,
    generate_memory,
    kernel_regions,
    make_corpus,
)
from repro.fuzz.engine import batched_oracle, batched_oracle_iterations
from repro.fuzz.triage import shrink

ensure_registered()

CFG = MapperConfig(per_ii_timeout_s=60.0, total_timeout_s=120.0, ii_max=32)


@pytest.fixture(scope="module")
def compiled():
    """One shared Toolchain compile per kernel (mapping needs no jax)."""
    from repro.toolchain.session import Toolchain

    tc = Toolchain("4x4", CFG)
    cache = {}

    def get(name):
        if name not in cache:
            cr = tc.compile(name)
            assert cr.ok, f"{name}: {cr.status} ({cr.error})"
            cache[name] = (cr.program.builder, cr.mapping)
        return cache[name]

    return get


# ---------------------------------------------------------------------------
# corpus
# ---------------------------------------------------------------------------


def test_corpus_deterministic_and_shaped():
    a = make_corpus("dotprod", 12, seed=3)
    b = make_corpus("dotprod", 12, seed=3)
    assert a.shape == (12, 128) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, make_corpus("dotprod", 12, seed=4))


def test_corpus_strategies_cycle_and_validate():
    mems = make_corpus("dotprod", 10, seed=0)
    for i in range(10):
        np.testing.assert_array_equal(
            mems[i], generate_memory("dotprod", i, seed=0,
                                     strategy=STRATEGIES[i % 5]))
    with pytest.raises(ValueError, match="unknown corpus strategy"):
        generate_memory("dotprod", 0, strategy="bogus")
    with pytest.raises(ValueError, match="unknown corpus strategy"):
        make_corpus("dotprod", 4, strategies=("uniform", "bogus"))


def test_corpus_touches_only_declared_regions():
    regions = kernel_regions("dotprod")
    covered = np.zeros(128, bool)
    for r in regions:
        covered[r.base:r.base + r.length] = True
    for i in range(10):
        mem = generate_memory("dotprod", i, seed=1)
        assert not mem[~covered].any(), "values outside declared regions"


def test_corpus_fxp_kernel_clipped_to_declared_range():
    """ema_fxp (the FXPMUL kernel) must never see values outside its
    declared region range — outside it the JAX cycle step's int32
    product is a known front-end gap, not a mapping bug."""
    regions = kernel_regions("ema_fxp")
    for i in range(20):
        mem = generate_memory("ema_fxp", i, seed=0)
        for r in regions:
            vals = mem[r.base:r.base + r.length].astype(np.int64)
            assert vals.min() >= r.lo and vals.max() < r.hi


# ---------------------------------------------------------------------------
# batched oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(kernel_names()))
def test_batched_oracle_matches_serial_interpreter(name):
    prog = kernel_program(name)
    mems = make_corpus(name, 6, seed=7)
    vals, fmem = batched_oracle(prog, mems)
    for b in range(mems.shape[0]):
        serial_mem = [int(v) for v in mems[b]]
        serial_vals = prog._interpret(serial_mem)
        for nid, arr in vals.items():
            assert (int(arr[b]) & M32) == (serial_vals[nid] & M32), \
                f"{name}: node {nid}, mem {b}"
        np.testing.assert_array_equal(
            np.asarray(fmem[b], np.int64) & M32,
            np.array(serial_mem, np.int64) & M32)


def test_batched_oracle_iterations_final_matches():
    prog = kernel_program("bitcount")
    mems = make_corpus("bitcount", 3, seed=0)
    history = batched_oracle_iterations(prog, mems)
    assert len(history) == prog.trip
    vals, _ = batched_oracle(prog, mems)
    for nid, arr in vals.items():
        np.testing.assert_array_equal(
            np.asarray(history[-1][nid], np.int64) & M32,
            np.asarray(arr, np.int64) & M32)


def test_batched_body_reference_matches_python_reference():
    from repro.frontend.kernels import TRACED_KERNELS
    from repro.frontend.tracer import batched_reference

    for name, tk in sorted(TRACED_KERNELS.items()):
        mems = np.stack([tk.make_mem(seed) for seed in range(5)])
        bvals, bmems = batched_reference(tk.spec, tk.body, mems)
        for b in range(5):
            rvals, rmem = tk.reference([int(v) for v in mems[b]])
            for n, exp in rvals.items():
                assert (int(bvals[n][b]) & M32) == (exp & M32), (name, n, b)
            np.testing.assert_array_equal(
                np.asarray(bmems[b]) & M32,
                np.array(rmem, np.int64) & M32)


# ---------------------------------------------------------------------------
# shrinker (synthetic checks: no jax needed)
# ---------------------------------------------------------------------------


def _membership_check(targets):
    """Failing mask: rows equal to any target row."""
    def check(mems):
        mems = np.atleast_2d(np.asarray(mems))
        return np.array([any(np.array_equal(m, t) for t in targets)
                         for m in mems], bool)
    return check


def test_shrink_to_single_failing_memory():
    rng = np.random.RandomState(0)
    mems = rng.randint(0, 100, (64, 4))
    targets = [mems[17].copy()]
    mem, idx, probes = shrink(mems, _membership_check(targets))
    assert idx == 17
    np.testing.assert_array_equal(mem, mems[17])
    # bisection: O(log n) halvings, each at most 2 probes, plus the solo
    # confirmation — far fewer than the 64 probes of a linear scan
    assert probes <= 2 * 7 + 1


def test_shrink_multiple_failures_returns_one():
    rng = np.random.RandomState(1)
    mems = rng.randint(0, 100, (32, 4))
    targets = [mems[5].copy(), mems[29].copy()]
    mem, idx, _ = shrink(mems, _membership_check(targets))
    assert idx in (5, 29)
    assert _membership_check(targets)(mem[None, :]).all()


def test_shrink_respects_corpus_indices():
    rng = np.random.RandomState(2)
    mems = rng.randint(0, 100, (8, 4))
    targets = [mems[3].copy()]
    _, idx, _ = shrink(mems, _membership_check(targets),
                       indices=[100, 101, 102, 103, 104, 105, 106, 107])
    assert idx == 103


def test_shrink_batch_coupled_failure_raises():
    mems = np.zeros((8, 4), np.int64)

    def coupled(batch):
        batch = np.atleast_2d(np.asarray(batch))
        n = batch.shape[0]
        return np.full(n, n > 1, bool)   # fails only in company

    with pytest.raises(ValueError, match="batch-coupled"):
        shrink(mems, coupled)


def test_shrink_no_failure_raises():
    mems = np.zeros((4, 4), np.int64)
    with pytest.raises(ValueError):
        shrink(mems, lambda m: np.zeros(np.atleast_2d(m).shape[0], bool))


# ---------------------------------------------------------------------------
# neighbor tables: the mesh seam
# ---------------------------------------------------------------------------


def test_mesh_neighbor_table_has_no_wraparound():
    from repro.archspec import parse_arch
    from repro.cgra import make_grid
    from repro.cgra.simulator import neighbor_table

    torus = neighbor_table(make_grid(4, 4))
    mesh = neighbor_table(parse_arch("mesh-4x4").grid())
    # torus: PE 0's north wraps to the bottom row, west to column 3
    assert torus[0] == (12, 1, 4, 3)
    # mesh: off-grid directions wire back to the PE itself
    assert mesh[0] == (0, 1, 4, 0)
    assert mesh[15] == (11, 15, 15, 14)
    assert mesh[3] == (3, 3, 7, 2)
    # interior PEs agree between the two topologies
    assert mesh[5] == torus[5] == (1, 6, 9, 4)


# ---------------------------------------------------------------------------
# energy: activity-based dynamic scaling
# ---------------------------------------------------------------------------


def test_energy_activity_none_is_byte_identical(compiled):
    from repro.cgra.energy import metrics_for_mapping

    prog, mapping = compiled("bitcount")
    legacy = metrics_for_mapping(prog, mapping)
    explicit = metrics_for_mapping(prog, mapping, activity=None)
    assert legacy.to_dict() == explicit.to_dict()


def test_energy_activity_scales_dynamic_only(compiled):
    from repro.cgra.bitstream import assemble
    from repro.cgra.energy import metrics_for_mapping

    prog, mapping = compiled("bitcount")
    static = metrics_for_mapping(prog, mapping)
    ops = [op for op in assemble(prog, mapping).op_counts() if op != "NOP"]
    half = {"result_toggle": {op: 0.25 for op in ops},
            "operand_toggle": {op: 0.25 for op in ops}}
    ref = {"result_toggle": {op: 0.5 for op in ops},
           "operand_toggle": {op: 0.5 for op in ops}}
    emp_half = metrics_for_mapping(prog, mapping, activity=half)
    emp_ref = metrics_for_mapping(prog, mapping, activity=ref)
    assert emp_half.static_nj == static.static_nj
    assert emp_half.dynamic_nj == pytest.approx(static.dynamic_nj / 2)
    assert emp_ref.dynamic_nj == pytest.approx(static.dynamic_nj)


# ---------------------------------------------------------------------------
# jax-gated: batched execution, fault injection, activity harvesting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch", [1, 7, 64])
def test_fuzz_verdicts_match_per_seed_verify(compiled, batch):
    pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
    from repro.cgra.simulator import verify
    from repro.fuzz.engine import fuzz_program

    prog, mapping = compiled("bitcount")
    n = max(batch, 8)
    mems = make_corpus("bitcount", n, seed=0)
    rep = fuzz_program(prog, mapping, mems, batch=batch,
                       collect_activity=False)
    assert rep.status == "ok" and rep.failing == []
    for i in range(min(n, 8)):
        assert verify(prog, mapping, mems[i]) == []


def test_batched_verdicts_independent_of_batch_size(compiled):
    """An injected fault is flagged for exactly the same memories at
    batch sizes 1, 7 and 64 — batching cannot change verdicts."""
    pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
    from repro.cgra.bitstream import assemble
    from repro.fuzz.triage import engine_check, inject_fault

    prog, mapping = compiled("bitcount")
    mut, _, _ = inject_fault(assemble(prog, mapping))
    check = engine_check(prog, mapping, asm=mut)
    mems = make_corpus("bitcount", 64, seed=0)
    mask64 = check(mems)
    assert mask64.any(), "injected fault went undetected"
    mask7 = np.concatenate([check(mems[lo:lo + 7])
                            for lo in range(0, 64, 7)])
    np.testing.assert_array_equal(mask7, mask64)
    for i in (0, 13, 63):
        assert bool(check(mems[i][None, :])[0]) == bool(mask64[i])


def test_fault_injection_shrinks_to_one_memory(compiled, tmp_path):
    pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
    from repro.cgra.bitstream import assemble
    from repro.fuzz.engine import FuzzReport, fuzz_program
    from repro.fuzz.triage import inject_fault, triage_failure

    prog, mapping = compiled("bitcount")
    mut, cell, label = inject_fault(assemble(prog, mapping))
    mems = make_corpus("bitcount", 32, seed=0)
    rep = fuzz_program(prog, mapping, mems, batch=16, asm=mut,
                       collect_activity=False)
    assert rep.status == "mismatch" and rep.failing
    assert rep.mismatches, "mismatch sample lines missing"
    triage_failure(prog, mapping, mems, rep, out_dir=str(tmp_path),
                   asm=mut)
    assert rep.divergence is not None
    assert (rep.divergence["cycle"], rep.divergence["pe"]) == cell
    assert rep.reproducer
    doc = json.loads(open(rep.reproducer).read())
    assert doc["kernel"] == "bitcount"
    assert len(doc["mem"]) == 128          # a single memory image
    assert doc["divergence"] == rep.divergence
    assert doc["mismatches"]


def test_stacked_verdicts_match_single_kernel_runs(compiled):
    pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
    from repro.fuzz.engine import fuzz_program, fuzz_stacked

    names = ["bitcount", "dotprod"]
    progs, maps, mems = [], [], []
    for n in names:
        p, m = compiled(n)
        progs.append(p)
        maps.append(m)
        mems.append(make_corpus(n, 24, seed=0))
    stacked = fuzz_stacked(progs, maps, np.stack(mems))
    for n, p, m, mm, srep in zip(names, progs, maps, mems, stacked):
        single = fuzz_program(p, m, mm, batch=24, collect_activity=False)
        assert srep.status == single.status == "ok"
        assert srep.failing == single.failing
        assert srep.ii == single.ii


def test_activity_counts_match_op_counts(compiled):
    pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
    from repro.cgra.bitstream import assemble
    from repro.fuzz.engine import fuzz_program

    prog, mapping = compiled("bitcount")
    B = 16
    mems = make_corpus("bitcount", B, seed=0)
    rep = fuzz_program(prog, mapping, mems, batch=B)
    assert rep.status == "ok"
    counts = assemble(prog, mapping).op_counts()
    expected = {op: c * B for op, c in counts.items()}
    assert rep.activity["op_exec"] == expected
    for op, rate in rep.activity["result_toggle"].items():
        assert 0.0 <= rate <= 1.0, (op, rate)
    for op, rate in rep.activity["operand_toggle"].items():
        assert 0.0 <= rate <= 1.0, (op, rate)


def test_fuzz_kernel_reports_energy_delta():
    pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
    from repro.fuzz.engine import fuzz_kernel

    rep = fuzz_kernel("bitcount", memories=32, batch=16, config=CFG)
    assert rep.status == "ok"
    e = rep.energy
    assert set(e) == {"static_dynamic_nj", "empirical_dynamic_nj",
                      "delta_nj", "delta_pct", "static_total_nj",
                      "empirical_total_nj"}
    assert e["delta_nj"] == pytest.approx(
        e["empirical_dynamic_nj"] - e["static_dynamic_nj"], abs=1e-3)


def test_mesh_arch_cosimulates_across_the_seam():
    """End-to-end on mesh-4x4: edge PEs must not observe wrapped values
    (the neighbor table used to hard-code the torus)."""
    pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
    from repro.fuzz.engine import fuzz_kernel

    rep = fuzz_kernel("dotprod", arch="mesh-4x4", memories=24, batch=24,
                      config=CFG)
    assert rep.status == "ok", rep.mismatches[:3]
    assert rep.failing == []


def test_fuzz_cli_writes_gateable_artifact(tmp_path):
    pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
    from repro.fuzz.cli import main as fuzz_main

    out = tmp_path / "fuzz.json"
    rc = fuzz_main(["--kernels", "bitcount", "--memories", "16",
                    "--batch", "8", "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["bench"] == "fuzz"
    assert doc["mismatches"] == 0 and doc["unmapped"] == 0
    (row,) = doc["results"]
    assert row["kernel"] == "bitcount" and row["status"] == "ok"
    assert row["energy"] and row["activity"]


def test_cosimulate_uses_batched_reference():
    pytest.importorskip("jax", reason="optional extra: pip install .[jax]")
    from repro.frontend.kernels import TRACED_KERNELS
    from repro.frontend.verify import cosimulate

    rep = cosimulate(TRACED_KERNELS["dotprod"], seeds=4, config=CFG)
    assert rep.status == "ok"
    assert rep.seeds == 4
    assert rep.mismatches == []
