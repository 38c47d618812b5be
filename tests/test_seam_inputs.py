"""The execution seam's inputs, built on the host: ``decode_fields`` cuts
the instruction fields out of the word grid by the ISA's bit layout, and
``preset_state`` builds the initial PE-array state in numpy.  Each is held
bit for bit to the path it replaced: one ``Instr`` per word
(``decode_program``), and a state made on the device whose OUT and
registers were copied back to set the presets."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.cgra.isa import OPCODE, OPS, Instr, decode_program  # noqa: E402
from repro.cgra.registry import kernel_names, make_mem  # noqa: E402
from repro.cgra.simulator import (execute_asm, neighbor_table,  # noqa: E402
                                  preset_state)
from repro.core.mapper import MapperConfig  # noqa: E402
from repro.fuzz.engine import run_stacked  # noqa: E402
from repro.kernels.ops import decode_fields, run_program  # noqa: E402
from repro.kernels.ref import InstrRow, PEState  # noqa: E402
from repro.obs import trace  # noqa: E402
from repro.obs.report import load  # noqa: E402

CFG = MapperConfig(per_ii_timeout_s=60.0, total_timeout_s=120.0, ii_max=32)
# the CDCL mapper finds no mapping for these within CFG on 4x4 or 6x6
UNMAPPED = ("sqrt", "sha", "sha2")
KERNELS = [k for k in kernel_names() if k not in UNMAPPED] + ["gsm_frame"]
ARCHS = ("4x4", "6x6")


@pytest.fixture(scope="module")
def compiled():
    """One Toolchain compile per (arch, kernel), shared by the tests."""
    from repro.toolchain.session import Toolchain

    chains = {arch: Toolchain(arch, CFG) for arch in ARCHS}
    cache = {}

    def get(arch, name):
        if (arch, name) not in cache:
            cr = chains[arch].compile(name)
            assert cr.ok, f"{arch} {name}: {cr.status} ({cr.error})"
            cache[arch, name] = cr
        return cache[arch, name]

    return get


def _decode_by_instr(words):
    """The replaced decode: one ``Instr`` per word, its fields copied out."""
    rows = decode_program(words)
    T, P = len(rows), len(rows[0])
    f = {name: np.zeros((T, P), np.int32) for name in InstrRow._fields}
    for t, row in enumerate(rows):
        for p, ins in enumerate(row):
            f["op"][t, p] = OPCODE[ins.op]
            f["dst"][t, p] = ins.dst
            f["sa"][t, p] = ins.src_a
            f["sb"][t, p] = ins.src_b
            f["imm"][t, p] = ins.imm
    return InstrRow(**f)


def _preset_on_device(asm, num_pes, mem, batch):
    """The replaced preset: zeros and the memory made on the device, OUT
    and the registers copied back to set the presets."""
    mem = np.asarray(mem, np.int32)
    if mem.ndim == 1:
        mem = np.broadcast_to(mem, (batch,) + mem.shape)
    state = PEState(regs=jnp.zeros((batch, num_pes, 4), jnp.int32),
                    out=jnp.zeros((batch, num_pes), jnp.int32),
                    sf=jnp.zeros((batch, num_pes), jnp.int32),
                    zf=jnp.zeros((batch, num_pes), jnp.int32),
                    mem=jnp.asarray(mem))
    out0 = np.array(state.out)
    regs0 = np.array(state.regs)
    for pe, val in asm.presets_out.items():
        out0[:, pe] = val
    for (pe, reg), val in asm.presets_reg.items():
        regs0[:, pe, reg] = val
    return state._replace(out=out0, regs=regs0)


def _assert_fields_equal(got, want):
    for name, g, w in zip(InstrRow._fields, got, want):
        assert isinstance(g, np.ndarray) and g.dtype == np.int32, name
        assert g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)


def test_opcode_id_is_the_position_in_ops():
    assert [OPCODE[op] for op in OPS] == list(range(len(OPS)))


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("arch", ARCHS)
def test_decode_fields_matches_instr_decode_on_kernels(compiled, arch,
                                                       kernel):
    words = compiled(arch, kernel).asm.words()
    _assert_fields_equal(decode_fields(words), _decode_by_instr(words))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_decode_fields_matches_instr_decode_on_random_words(seed):
    """Every opcode, dst 0-7, selectors 0-15 and the immediate's extremes,
    in a (256, 16) grid."""
    rng = np.random.RandomState(seed)
    n = 256 * 16
    op = rng.permutation(np.arange(n) % len(OPS))
    dst = rng.permutation(np.arange(n) % 8)
    sa = rng.permutation(np.arange(n) % 16)
    sb = rng.permutation(np.arange(n) % 16)
    imm = rng.randint(-2**15, 2**15, n)
    imm[rng.choice(n, 4, replace=False)] = [-2**15, -1, 0, 2**15 - 1]
    words = np.array([Instr(op=OPS[o], dst=int(d), src_a=int(a),
                            src_b=int(b), imm=int(i)).encode()
                      for o, d, a, b, i in zip(op, dst, sa, sb, imm)],
                     np.uint32).reshape(256, 16)
    got = decode_fields(words)
    _assert_fields_equal(got, _decode_by_instr(words))
    for field, want in zip(got, (op, dst, sa, sb, imm)):
        np.testing.assert_array_equal(field.ravel(), want)


@pytest.mark.parametrize("field", range(len(OPS), 32))
def test_decode_fields_refuses_an_opcode_field_outside_ops(field):
    words = np.full((3, 4), Instr(op="NOP").encode(), np.uint32)
    words[1, 2] = np.uint32(field << 27)
    with pytest.raises(IndexError):
        decode_program(words)
    with pytest.raises(ValueError, match="names no op"):
        decode_fields(words)


@pytest.mark.parametrize("batch,mem_ndim", [(1, 1), (1, 2), (128, 1),
                                            (128, 2)])
@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("arch", ARCHS)
def test_preset_state_matches_the_device_built_state(compiled, arch, kernel,
                                                     batch, mem_ndim):
    asm = compiled(arch, kernel).asm
    P = asm.num_pes
    mem = make_mem(kernel, seed=batch)
    if mem_ndim == 2:
        mem = np.stack([make_mem(kernel, seed=s) for s in range(batch)])
    want = _preset_on_device(asm, P, mem, batch)
    with jax.transfer_guard("disallow"):
        got = preset_state(asm, P, mem, batch)
    for name, g, w in zip(PEState._fields, got, want):
        assert isinstance(g, np.ndarray) and g.dtype == np.int32, name
        assert g.shape == w.shape, name
        np.testing.assert_array_equal(g, np.asarray(w), err_msg=name)


def test_preset_span_copies_nothing_back(compiled, tmp_path):
    """The seam's inputs are made with nothing moved in either direction,
    and ``verify.preset`` records no bytes copied back."""
    cr = compiled("6x6", "gsm")
    mems = np.stack([make_mem("gsm", seed=s) for s in range(4)])
    with jax.transfer_guard("disallow"):
        decode_fields(cr.asm.words())
        preset_state(cr.asm, cr.asm.num_pes, mems, 4)
    trace.enable(str(tmp_path))
    try:
        execute_asm(cr.asm, cr.mapping.grid, mems, batch=4)
    finally:
        trace.disable()
    presets = [r for r in load(str(tmp_path))
               if r["k"] == "span" and r["name"] == "verify.preset"]
    assert len(presets) == 1
    assert presets[0]["attrs"].get("d2h_bytes", 0) == 0


def test_run_stacked_matches_the_replaced_inputs(compiled):
    """gsm (84 rows) and stencil3 (112) on the 4x4 torus in one stacked
    dispatch give what each gives alone from the replaced decode and
    preset."""
    names = ("gsm", "stencil3")
    crs = [compiled("4x4", k) for k in names]
    grid = crs[0].mapping.grid
    B = 8
    mems = np.stack([np.stack([make_mem(k, seed=s) for s in range(B)])
                     for k in names])
    final, outs = run_stacked([cr.asm for cr in crs], grid, mems)
    nbrs = neighbor_table(grid)
    for k, cr in enumerate(crs):
        fields = jax.tree_util.tree_map(jnp.asarray,
                                        _decode_by_instr(cr.asm.words()))
        state = _preset_on_device(cr.asm, grid.num_pes, mems[k], B)
        want_final, want_outs = run_program(fields, state, nbrs)
        T = len(cr.asm.rows)
        for name, g, w in zip(PEState._fields, final, want_final):
            np.testing.assert_array_equal(np.asarray(g[k]), np.asarray(w),
                                          err_msg=name)
        np.testing.assert_array_equal(outs[k, :T], np.asarray(want_outs))
        np.testing.assert_array_equal(
            outs[k, T:], np.broadcast_to(outs[k, T - 1], outs[k, T:].shape))
