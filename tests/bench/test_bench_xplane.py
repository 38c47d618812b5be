"""The reduction of a profiler trace to device numbers (bench/xplane.py),
on a hand-made trace and on a slice recorded on a TPU v5e."""
import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import xplane  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "tpu_v5e_fleet_slice.json.gz"
LABELS = {"job", "seam", "seam.decode", "seam.preset", "seam.dispatch",
          "oracle", "harvest.compare", "harvest.nodes", "harvest.activity"}


def _made():
    host = ("/host:CPU", [("python3", [
        ("window", 100.0, 1000.0),
        ("job", 100.0, 900.0),
        ("seam", 120.0, 300.0),
        ("harvest.activity", 500.0, 400.0),
        ("unrelated", 600.0, 10.0),
    ])])
    device = ("/device:TPU:0", [
        ("XLA Modules", [("jit_run_program(42)", 200.0, 200.0)]),
        ("XLA Ops", [("%while.3 = (s32[]) while(...)", 200.0, 150.0),
                     ("%fusion.1 = s32[] fusion(...)", 300.0, 100.0),
                     ("%copy.2 = s32[] copy(...)", 50.0, 80.0),
                     ("%copy.2 = s32[] copy(...)", 1050.0, 100.0)]),
    ])
    empty = ("/device:CUSTOM:Megascale Trace", [("Steps", [])])
    return [host, device, empty]


def test_made_trace():
    r = xplane.reduce(_made(), LABELS)
    assert r.devices == 1
    assert r.window_ns == 1000.0
    # busy: [100, 130) + [200, 400) + [1050, 1100) inside [100, 1100)
    assert r.busy_ns == 30.0 + 200.0 + 50.0
    assert r.device_ops[0] == ("jit_run_program/while.3", 150e-9)
    assert ("copy.2", 80e-9) in r.device_ops
    # gaps [130, 200) in seam, [400, 1050) middle 725 in harvest.activity
    assert r.idle_gaps == [("harvest.activity", 650e-9), ("seam", 70e-9)]


def test_no_window_or_no_device_reads_nothing():
    host, device, empty = _made()
    assert xplane.reduce([device, empty], LABELS) is None
    assert xplane.reduce([host, empty], LABELS) is None


def test_short_names():
    assert xplane.short_name("%fusion.75 = s32[131072]{0} fusion(x)") \
        == "fusion.75"
    assert xplane.short_name("jit_run_program(4978)") == "jit_run_program"


def test_recorded_tpu_slice():
    with gzip.open(RECORDED, "rt") as fh:
        planes = json.load(fh)
    r = xplane.reduce(planes, LABELS)
    assert r is not None and r.devices == 1
    (w0, w1), = [(s, s + d) for p, lines in planes for _, ev in lines
                 for n, s, d in ev if n == "window"]
    assert r.window_ns == w1 - w0
    # busy against a 1 us grid
    grid = np.zeros(int((w1 - w0) // 1000) + 1, bool)
    ops = [ev for p, lines in planes if p == "/device:TPU:0"
           for name, ev in lines if name == "XLA Ops"][0]
    for _, s, d in ops:
        lo, hi = max(s, w0), min(s + d, w1)
        if hi > lo:
            grid[int((lo - w0) // 1000):int(np.ceil((hi - w0) / 1000))] = True
    assert r.busy_ns == pytest.approx(grid.sum() * 1000, rel=0.02)
    assert 0 < r.busy_ns < r.window_ns
    assert r.device_ops[0][0].startswith("jit_run_program/while")
    assert len(r.device_ops) <= xplane.TOP and len(r.idle_gaps) <= xplane.TOP
    assert {g for g, _ in r.idle_gaps} <= LABELS | {"-"}
    assert sum(t for _, t in r.idle_gaps) <= (r.window_ns - r.busy_ns) / 1e9


def test_load_reads_a_recorded_trace(tmp_path):
    """A trace recorded here (CPU: no device plane) loads, keeps the host
    spans, and reduces to nothing."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("seam"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    planes = xplane.load(str(tmp_path))
    names = {n for _, lines in planes for _, ev in lines for n, _, _ in ev}
    assert {"window", "seam"} <= names
    assert xplane.reduce(planes, LABELS) is None
