"""The verify path's own spans (``repro.obs``): one ``verify.job`` tree per
``fuzz_program`` call, the bytes copied back from the device, the time
the tree accounts for, the profiler annotation each span also is, and the
disabled path that writes nothing but still times the seam and oracle."""
import sys
import types
from pathlib import Path

import pytest

pytest.importorskip("jax")

from repro.cgra.registry import ensure_registered  # noqa: E402
from repro.core.mapper import MapperConfig  # noqa: E402
from repro.fuzz.corpus import make_corpus  # noqa: E402
from repro.fuzz.engine import fuzz_program  # noqa: E402
from repro.obs import trace  # noqa: E402
from repro.obs.report import attribution, load, validate  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
KERNEL = "dotprod"
BATCH = 8
MEMORIES = 2 * BATCH              # two chunks

# span -> the name of its parent
PARENT = {
    "verify.job": None,
    "verify.chunk": "verify.job",
    "verify.seam": "verify.chunk",
    "verify.decode": "verify.seam",
    "verify.preset": "verify.seam",
    "verify.dispatch": "verify.seam",
    "verify.wait": "verify.seam",
    "verify.nodes": "verify.chunk",
    "verify.oracle": "verify.chunk",
    "verify.compare": "verify.chunk",
    "verify.activity": "verify.chunk",
}
LEAVES = ("verify.decode", "verify.preset", "verify.dispatch", "verify.wait",
          "verify.transfer", "verify.nodes", "verify.oracle",
          "verify.compare", "verify.activity")
OWN = ("verify.job", "verify.chunk", "verify.seam")


@pytest.fixture(scope="module")
def compiled():
    from repro.toolchain.session import Toolchain

    ensure_registered()
    tc = Toolchain("4x4", MapperConfig(per_ii_timeout_s=60.0,
                                       total_timeout_s=120.0, ii_max=32))
    cr = tc.compile(KERNEL)
    assert cr.ok, cr.error
    mems = make_corpus(KERNEL, MEMORIES, seed=7)
    # compile the chunk shape outside the traced call
    fuzz_program(cr.program.builder, cr.mapping, mems[:BATCH], batch=BATCH)
    return cr, mems


@pytest.fixture(scope="module")
def traced(compiled, tmp_path_factory):
    """One traced call of two chunks, under a CPU profiler trace too."""
    import jax

    cr, mems = compiled
    spans_dir = tmp_path_factory.mktemp("spans")
    profile_dir = tmp_path_factory.mktemp("profile")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace.enable(str(spans_dir))
    try:
        jax.profiler.start_trace(str(profile_dir), profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("window"):
                rep = fuzz_program(cr.program.builder, cr.mapping, mems,
                                   batch=BATCH, kernel=KERNEL)
        finally:
            jax.profiler.stop_trace()
    finally:
        trace.disable()
    return rep, load(str(spans_dir)), str(profile_dir)


def _by_id(records):
    return {r["span"]: r for r in records if r["k"] == "span"}


def test_span_tree_parentage_and_one_trace(compiled, traced):
    cr, _ = compiled
    rep, records, _ = traced
    assert rep.ok and rep.memories == MEMORIES
    assert validate(records) == []
    spans = _by_id(records)
    names = [r["name"] for r in spans.values()]
    counts = {n: names.count(n) for n in set(names)}
    assert counts == dict({n: 2 for n in PARENT}, **{
        "verify.job": 1, "verify.transfer": 4})
    assert len({r["trace"] for r in records}) == 1
    for r in spans.values():
        parent = spans.get(r["parent"], {}).get("name")
        if r["name"] == "verify.transfer":
            assert parent in ("verify.seam", "verify.chunk")
        else:
            assert parent == PARENT[r["name"]], r["name"]
    parents = sorted(spans[r["parent"]]["name"] for r in spans.values()
                     if r["name"] == "verify.transfer")
    assert parents == ["verify.chunk"] * 2 + ["verify.seam"] * 2
    job = next(r for r in spans.values() if r["name"] == "verify.job")
    assert job["attrs"] == {"kernel": KERNEL, "memories": MEMORIES,
                            "batch": BATCH,
                            "trip": cr.program.builder.trip,
                            "mem_words": 128}
    for r in spans.values():
        if r["name"] == "verify.chunk":
            assert r["attrs"] == {"memories": BATCH,
                                  "rows": len(cr.asm.rows), "pes": 16,
                                  "mem_words": 128}


def test_d2h_bytes_match_the_formula(compiled, traced):
    """(T*P + M + 5P) * 4 bytes a memory: the OUT trace, the final memory
    and the preset OUT and register state."""
    cr, mems = compiled
    _, records, _ = traced
    t, p, m = len(cr.asm.rows), 16, mems.shape[1]
    total = sum(r["attrs"].get("d2h_bytes", 0) for r in records)
    assert total == MEMORIES * (t * p + m + 5 * p) * 4


def test_leaves_and_self_time_make_up_the_job(traced):
    _, records, _ = traced
    table = attribution(records)["by_name"]
    job = table["verify.job"]["total_s"]
    parts = (sum(table[n]["total_s"] for n in LEAVES)
             + sum(table[n]["self_s"] for n in OWN))
    assert job > 0
    assert abs(parts - job) <= 0.01 * job


def test_spans_are_profiler_annotations(traced):
    sys.path.insert(0, str(ROOT))
    from bench import xplane

    _, _, profile_dir = traced
    planes = xplane.load(profile_dir)
    names = {n for _, lines in planes for _, ev in lines for n, _, _ in ev}
    assert {"verify.job", "verify.decode", "verify.wait"} <= names


def test_annotation_only_when_jax_is_imported(monkeypatch, tmp_path):
    """A span enters a TraceAnnotation of its name when ``jax`` is in
    ``sys.modules``, and nothing of JAX otherwise."""
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    fake = types.SimpleNamespace(
        profiler=types.SimpleNamespace(TraceAnnotation=Annotation))
    trace.enable(str(tmp_path))
    try:
        monkeypatch.setitem(sys.modules, "jax", fake)
        with trace.span("outer"):
            with trace.timed_span("inner"):
                pass
        monkeypatch.delitem(sys.modules, "jax")
        with trace.span("unseen"):
            pass
    finally:
        trace.disable()
    assert entered == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner"), ("exit", "outer")]


def test_tracing_off_writes_nothing_and_still_times(compiled, monkeypatch):
    cr, mems = compiled
    written = []
    monkeypatch.setattr(trace, "_write", written.append)
    trace.disable()
    rep = fuzz_program(cr.program.builder, cr.mapping, mems, batch=BATCH)
    assert rep.ok and written == []
    assert rep.exec_time_s > 0 and rep.oracle_time_s > 0


def test_tally_sums_timed_spans_with_tracing_off():
    trace.disable()
    with trace.tally() as seconds:
        for _ in range(3):
            with trace.timed_span("a"):
                pass
        with trace.span("b"):
            pass
    assert set(seconds) == {"a"} and seconds["a"] > 0
    total = seconds["a"]
    with trace.timed_span("a"):
        pass
    assert seconds["a"] == total
