"""gsm over whole GSM 06.10 frames (``gsm_frame``: trip 160, 512 words)
through the normal path, held word for word to a plain numpy reference
written from gsm's equations; and guards that the refactor of ``gsm``
and of the corpus left every existing kernel's DFG and image as it was.

Every comparison here is exact (``array_equal`` on 32-bit words): the
program claims bit-exact int32 semantics, so any difference is a fault.
"""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.cgra.programs import benchmark_mem, gsm
from repro.cgra.registry import (
    ensure_registered,
    get_kernel,
    kernel_names,
    make_mem,
)
from repro.core.mapper import MapperConfig
from repro.fuzz.corpus import kernel_regions, make_corpus
from repro.fuzz.engine import batched_oracle

ensure_registered()

ROOT = Path(__file__).resolve().parents[1]
CFG = MapperConfig(per_ii_timeout_s=60.0, total_timeout_s=120.0, ii_max=32)
M32 = (1 << 32) - 1
TRIP, X_BASE, Y_BASE, OUT_BASE, WORDS = 160, 0, 160, 320, 512
Q15_MAX, Q15_MIN = 32767, -32768


def _wrap32(x):
    x = np.asarray(x, np.int64) & M32
    return x - ((x >= 1 << 31).astype(np.int64) << 32)


def frame_mac(mems, trip=TRIP, x_base=X_BASE, y_base=Y_BASE,
              out_base=OUT_BASE):
    """Plain reference of gsm's saturating MAC over a batch of memories.

    For i in 0..trip-1, in int64 wrapped to 32-bit words::

        prod = x[i] * y[i];  sh = prod >> 15;  s = acc + sh
        s2 = s saturated to [-32768, 32767];   mem[out_base + i + 1] = s2
        acc = s2

    Returns (final memories (B, M), last ``acc`` (B,)).

    Departures from the paper's description (a 14-node / 20-edge DFG of
    the GSM codec's MAC, arXiv:2402.12834 Table 6): the program has 13
    nodes, saturates with two BSFA selects on the sign of ``s - 32767``
    and ``s1 + 32768`` (the same as the clamp here, since ``acc`` stays in
    16 bits and ``s`` cannot wrap), and stores each running sum one word
    past its sample, at ``out_base + i + 1``, because the store's address
    is the incremented index.
    """
    mem = _wrap32(np.array(mems, np.int64, ndmin=2))
    acc = np.zeros(mem.shape[0], np.int64)
    for i in range(trip):
        prod = _wrap32(mem[:, x_base + i] * mem[:, y_base + i])
        s = _wrap32(acc + (prod >> 15))
        acc = np.clip(s, Q15_MIN, Q15_MAX)
        mem[:, out_base + i + 1] = acc
    return mem, acc


# ---------------------------------------------------------------------------
# the reference itself, by hand
# ---------------------------------------------------------------------------


def test_plain_reference_by_hand():
    """x = y = 16383: each product adds (16383**2) >> 15 = 8191, so the
    sum reaches 32764 in four steps and saturates at 32767 from the fifth;
    x = -16384, y = 16383 subtracts 8192 a step down to -32768."""
    mem = np.zeros((2, WORDS), np.int64)
    mem[0, :TRIP] = mem[0, Y_BASE:Y_BASE + TRIP] = 16383
    mem[1, :TRIP], mem[1, Y_BASE:Y_BASE + TRIP] = -16384, 16383
    final, acc = frame_mac(mem)
    assert list(final[0, OUT_BASE + 1:OUT_BASE + 6]) == [
        8191, 16382, 24573, 32764, 32767]
    assert list(final[1, OUT_BASE + 1:OUT_BASE + 6]) == [
        -8192, -16384, -24576, -32768, -32768]
    assert list(acc) == [32767, -32768]
    assert (final[:, OUT_BASE + TRIP + 1:] == 0).all()
    assert final[0, OUT_BASE] == 0


# ---------------------------------------------------------------------------
# the program over whole frames, against the reference
# ---------------------------------------------------------------------------

# (memories, batch): two whole chunks at 32, one at 64, a single memory,
# and a ragged last chunk (50 = 32 + 18)
CASES = {"batch32": (64, 32), "batch64": (64, 64), "single": (1, 1),
         "ragged": (50, 32)}


@pytest.fixture(scope="module")
def frame():
    """gsm_frame mapped and assembled on the 4x4 torus."""
    from repro.toolchain.session import Toolchain

    cr = Toolchain("4x4", CFG).compile("gsm_frame")
    assert cr.ok, f"{cr.status} ({cr.error})"
    return cr


def _memories(case):
    n, _ = CASES[case]
    return make_corpus("gsm_frame", n, seed=sorted(CASES).index(case) + 11)


def test_gsm_frame_is_gsm_over_a_whole_frame(frame):
    prog = frame.program.builder
    assert prog.trip == TRIP and get_kernel("gsm_frame").mem_words == WORDS
    assert get_kernel("gsm_frame").variant_of == "gsm"
    assert prog.build_dfg().num_nodes == gsm().build_dfg().num_nodes
    # one II a loop iteration, plus the prologue and epilogue
    assert len(frame.asm.rows) > (TRIP - 1) * frame.asm.ii


@pytest.mark.parametrize("case", sorted(CASES))
def test_fuzz_program_matches_plain_reference(frame, case, monkeypatch):
    """``fuzz_program`` flags no memory, and what its
    execution seam produced (final memories, last-iteration acc) equals
    the plain reference word for word."""
    pytest.importorskip("jax")
    from repro.cgra import simulator
    from repro.fuzz.engine import fuzz_program, node_values_from_outs

    prog, asm = frame.program.builder, frame.asm
    mems = _memories(case)
    _, batch = CASES[case]
    seen = []
    execute_asm = simulator.execute_asm

    def capturing(*args, **kwargs):
        final, outs, out0 = execute_asm(*args, **kwargs)
        seen.append((np.asarray(final.mem),
                     node_values_from_outs(asm, outs, prog.trip)))
        return final, outs, out0

    monkeypatch.setattr(simulator, "execute_asm", capturing)
    rep = fuzz_program(prog, frame.mapping, mems, batch=batch,
                       kernel="gsm_frame")
    assert rep.ok and rep.failing == [], rep.mismatches[:3]
    assert rep.memories == len(mems) and rep.mem_words == WORDS
    assert len(seen) == -(-len(mems) // batch)
    want_mem, want_acc = frame_mac(mems)
    got_mem = np.concatenate([m for m, _ in seen])
    acc_node = prog.result_nodes["acc"]
    got_acc = np.concatenate([v[acc_node] for _, v in seen])
    assert got_mem.shape == (len(mems), WORDS)
    assert np.array_equal(np.asarray(got_mem, np.int64) & M32,
                          want_mem & M32)
    assert np.array_equal(np.asarray(got_acc, np.int64) & M32,
                          want_acc & M32)


@pytest.mark.parametrize("case", sorted(CASES))
def test_oracle_and_bench_reference_match_plain_reference(frame, case):
    sys.path.insert(0, str(ROOT))
    from bench import reference

    prog = frame.program.builder
    mems = _memories(case)
    want_mem, want_acc = frame_mac(mems)
    acc_node = prog.result_nodes["acc"]
    vals, final = batched_oracle(prog, mems)
    bvals, bfinal = reference.run(prog, mems)
    for got_mem, got_acc in ((final, vals[acc_node]),
                             (bfinal, bvals[acc_node])):
        assert np.array_equal(np.asarray(got_mem, np.int64) & M32,
                              want_mem & M32)
        assert np.array_equal(
            np.broadcast_to(got_acc, want_acc.shape) & M32, want_acc & M32)


def test_corpus_reaches_both_saturation_bounds():
    """The frame corpus drives the running sum into both clamps, so the
    two BSFA paths are exercised, not just the linear one."""
    final, _ = frame_mac(make_corpus("gsm_frame", 64, seed=3))
    sums = final[:, OUT_BASE + 1:OUT_BASE + TRIP + 1]
    assert (sums == Q15_MAX).any() and (sums == Q15_MIN).any()
    assert ((sums > Q15_MIN) & (sums < Q15_MAX)).any()


# ---------------------------------------------------------------------------
# regression guards on the shared code
# ---------------------------------------------------------------------------

# gsm() at its defaults as it was before it took array bases: its nodes
# (id, op, immediate, operands), edges, flag producers and carries
GSM_DEFAULT = {
    "name": "gsm", "trip": 16,
    "nodes": [
        [1, "LWI", 0, "(Carry(name='i', init=0, update=10), None)"],
        [2, "LWI", 32, "(Carry(name='i', init=0, update=10), None)"],
        [3, "SMUL", 0, "(Val(node=1), Val(node=2))"],
        [4, "SRA", 15, "(Val(node=3), None)"],
        [5, "SADD", 0, "(Carry(name='acc', init=0, update=9), Val(node=4))"],
        [6, "SSUB", 32767, "(Val(node=5), None)"],
        [7, "BSFA", 32767, "(Val(node=5), None)"],
        [8, "SSUB", -32768, "(Val(node=7), None)"],
        [9, "BSFA", -32768, "(None, Val(node=7))"],
        [10, "SADD", 1, "(Carry(name='i', init=0, update=10), None)"],
        [11, "SWI", 64, "(Val(node=10), Val(node=9))"],
        [12, "BNE", 16, "(Val(node=10), None)"],
        [13, "JUMP", 0, "(Val(node=12), None)"]],
    "edges": [
        [1, 3, 0, "data"], [2, 3, 0, "data"], [3, 4, 0, "data"],
        [4, 5, 0, "data"], [5, 6, 0, "data"], [5, 7, 0, "data"],
        [6, 7, 0, "flag"], [7, 8, 0, "data"], [7, 9, 0, "data"],
        [8, 9, 0, "flag"], [9, 5, 1, "data"], [9, 11, 0, "data"],
        [10, 1, 1, "data"], [10, 2, 1, "data"], [10, 10, 1, "data"],
        [10, 11, 0, "data"], [10, 12, 0, "data"], [12, 13, 0, "data"]],
    "flags": [[7, 6], [9, 8]],
    "carries": [["i", 0, 10], ["acc", 0, 9]],
}
# the 4x4 mapping-cache key of that DFG (the torus4x4 cells' mappings)
GSM_4X4_KEY = \
    "5258f669687e0eec87e401625e085a0b2a0a1c0ab0cce4a76b99338fe67c5196"
# sha256 of benchmark_mem("gsm", seed) before the refactor
GSM_MEM_SHA256 = {
    0: "98494c0c373d14fc4f60985ae14d86788572eaf928672d61108346ec0f7169f3",
    1: "03ca171ceb6a3c2e1d66e5066b715cbd67f8de6742215f7a69091bbffaef7d86",
    7: "55f558fbaaf19705cb331d49fd7f4262537bad49d394302e8b8a5751b7a27e80",
}


def test_gsm_default_dfg_is_unchanged():
    from repro.cgra.arch import make_grid
    from repro.core.mapper import mapping_cache_key

    p = gsm()
    dfg = p.build_dfg()
    got = {"name": p.name, "trip": p.trip,
           "nodes": [[n.id, n.op, p.node_imm[n.id], repr(p.node_srcs[n.id])]
                     for n in p.nodes],
           "edges": sorted([e.src, e.dst, e.distance, e.kind]
                           for e in dfg.edges),
           "flags": sorted([c, f] for c, f in p.flag_deps.items()),
           "carries": [[c.name, c.init, c.update] for c in p.carries]}
    assert json.loads(json.dumps(got)) == GSM_DEFAULT
    assert mapping_cache_key(dfg, make_grid(4, 4)) == GSM_4X4_KEY


@pytest.mark.parametrize("seed", sorted(GSM_MEM_SHA256))
def test_benchmark_mem_gsm_is_unchanged(seed):
    mem = benchmark_mem("gsm", seed)
    assert mem.shape == (128,) and mem.dtype == np.int32
    assert hashlib.sha256(mem.tobytes()).hexdigest() == GSM_MEM_SHA256[seed]
    assert np.array_equal(make_mem("gsm", seed), mem)


@pytest.mark.parametrize("name", sorted(kernel_names()))
def test_existing_kernels_keep_128_words(name):
    assert get_kernel(name).mem_words == 128
    assert make_corpus(name, 7, seed=1).shape == (7, 128)
    assert make_mem(name, 0).shape == (128,)


def test_variants_stay_out_of_the_default_suites():
    """gsm_frame is fetched by name but joins no "all kernels" default:
    the DSE sweep, ``repro fuzz --kernels all`` and the registry's suite
    list the same kernels as before it was registered."""
    from repro.dse.space import DEFAULT_KERNELS
    from repro.fuzz.cli import _resolve_kernels

    assert "gsm_frame" not in kernel_names()
    assert "gsm_frame" not in DEFAULT_KERNELS
    assert _resolve_kernels("all") == kernel_names()
    assert (set(kernel_names(variants=True)) - set(kernel_names())
            == {"gsm_frame"})
    assert _resolve_kernels("gsm_frame") == ["gsm_frame"]


def test_gsm_frame_corpus_layout():
    n = 40
    mems = make_corpus("gsm_frame", n, seed=5).astype(np.int64)
    assert mems.shape == (n, WORDS) and make_mem("gsm_frame", 0).shape == (
        WORDS,)
    regions = kernel_regions("gsm_frame")
    assert [(r.base, r.length, r.lo, r.hi) for r in regions] == [
        (0, 160, -(2 ** 14), 2 ** 14), (160, 160, -(2 ** 14), 2 ** 14)]
    inside = np.zeros(WORDS, bool)
    uniform = mems[0::5]                  # strategy 0 of five
    for r in regions:
        cols = slice(r.base, r.base + r.length)
        inside[cols] = True
        assert (mems[:, cols] != 0).any(axis=1).sum() > n // 2
        assert ((uniform[:, cols] >= r.lo) & (uniform[:, cols] < r.hi)).all()
    assert (mems[:, ~inside] == 0).all()


# ---------------------------------------------------------------------------
# the CLI, and its spans under REPRO_TRACE
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cli_run(tmp_path_factory):
    pytest.importorskip("jax")
    spans = tmp_path_factory.mktemp("frame-spans")
    env = dict(os.environ, REPRO_TRACE=str(spans), JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-m", "repro", "fuzz", "--kernels", "gsm_frame",
         "--arch", "4x4", "--memories", "64", "--batch", "32"],
        cwd=str(tmp_path_factory.mktemp("frame-cwd")), env=env,
        capture_output=True, text=True, timeout=600)
    return proc, str(spans)


def test_fuzz_cli_runs_gsm_frame_on_512_word_memories(cli_run):
    proc, _ = cli_run
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "gsm_frame @ 4x4: ok" in proc.stdout
    assert "64 memories of 512 words" in proc.stdout


def test_fuzz_cli_spans_carry_trip_and_mem_words(cli_run):
    from repro.obs.report import load

    proc, spans = cli_run
    assert proc.returncode == 0, proc.stdout + proc.stderr
    records = [r for r in load(spans) if r["k"] == "span"]
    jobs = [r for r in records if r["name"] == "verify.job"]
    chunks = [r for r in records if r["name"] == "verify.chunk"]
    assert len(jobs) == 1 and len(chunks) == 2
    assert jobs[0]["attrs"]["trip"] == TRIP
    assert jobs[0]["attrs"]["mem_words"] == WORDS
    assert all(c["attrs"]["mem_words"] == WORDS for c in chunks)
