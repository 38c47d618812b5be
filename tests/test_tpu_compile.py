"""Compile rehearsal of the batched verify path for a TPU v5e that is
described, not attached.

``run_program`` with the ref scan and with the compiled Pallas cycle step,
at the fabric sizes the verification fleet runs (4x4 and 6x6 torus), a
chunk of 8,192 memories of 128 words (the registry's memory size) and a
ragged last chunk; and gsm_frame's 804 rows (gsm at trip 160) over
8,192 memories of 512 words on the 4x4 torus.  Nothing runs: a pass says
that the chip's compiler accepts the programs, that the Pallas step fits
its scoped VMEM and that the ref scan holds no gather and no scatter, not
that results are right (tests/test_kernels.py checks those in interpret
mode on the CPU).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.cgra import make_grid  # noqa: E402
from repro.cgra.simulator import neighbor_table  # noqa: E402
from repro.kernels import ops, pe_array  # noqa: E402
from repro.kernels.ref import InstrRow, PEState  # noqa: E402

ROWS = 112          # stencil3's bitstream on the 4x4 torus, the longest smoked
MEM_WORDS = 128     # cgra/programs.py benchmark_mem
FRAME_ROWS = 804    # gsm_frame's bitstream on the 4x4 torus (trip 160)
FRAME_MEM_WORDS = 512
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e 2x2, with JAX's persistent cache off
    (a compile for a described chip is written but cannot be read back)
    and JAX's tracing caches cleared on both sides, so no program traced
    here in compiled mode is reused by an interpreting test, or back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler, or the library is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        jax.clear_caches()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.clear_caches()
            jax.config.update("jax_enable_compilation_cache", was_enabled)
            compilation_cache.reset_cache()


def _compile(one_chip, monkeypatch, n, batch, backend, rows=ROWS,
             mem_words=MEM_WORDS):
    # the program asks the default backend (here the CPU) whether to
    # interpret; this chip is described, so steer it to the compiled step
    monkeypatch.setattr(pe_array, "interpret_mode", lambda: False)
    grid = make_grid(n, n)
    P = grid.num_pes

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    fields = InstrRow(*(spec((rows, P)) for _ in range(5)))
    state = PEState(regs=spec((batch, P, 4)), out=spec((batch, P)),
                    sf=spec((batch, P)), zf=spec((batch, P)),
                    mem=spec((batch, mem_words)))
    return ops.run_program.lower(fields, state, neighbor_table(grid),
                                 backend=backend).compile()


@pytest.mark.parametrize("n", [4, 6])
@pytest.mark.parametrize("batch", [8192, 7232])
def test_pallas_step_compiles_for_v5e(one_chip, monkeypatch, n, batch):
    compiled = _compile(one_chip, monkeypatch, n, batch, "pallas")
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("n", [4, 6])
def test_ref_scan_compiles_for_v5e(one_chip, monkeypatch, n):
    compiled = _compile(one_chip, monkeypatch, n, 8192, "ref")
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_frame_program_compiles_for_v5e(one_chip, monkeypatch, backend):
    compiled = _compile(one_chip, monkeypatch, 4, 8192, backend,
                        rows=FRAME_ROWS, mem_words=FRAME_MEM_WORDS)
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("n,mem_words", [(4, MEM_WORDS), (6, MEM_WORDS),
                                         (4, FRAME_MEM_WORDS)])
def test_ref_scan_holds_no_gather_or_scatter(one_chip, monkeypatch, n,
                                             mem_words):
    """The ref step picks operands, ALU results, loaded words and stored
    words by compare-and-select: on the chip an index gather or scatter in
    the scan body costs per index, not per byte (PERF.md section 5)."""
    hlo = _compile(one_chip, monkeypatch, n, 8192, "ref",
                   mem_words=mem_words).as_text()
    assert "gather(" not in hlo
    assert "scatter(" not in hlo
