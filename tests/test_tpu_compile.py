"""Compile the main path's kernels and serving steps for a described TPU v5e
chip, with no chip attached.

The TPU compiler refuses what interpret mode accepts: scalar stores to
VMEM, unaligned DMA slices, in-kernel gathers, programs larger than the
chip's memory.  Each test here lowers with ``interpret=False`` and
compiles against one chip of a described ``v5e:2x2`` topology, so such a
refusal fails the suite instead of a run on the chip.  The topology is
described inside a fixture, never while a module is imported, and the
persistent compilation cache is off around these compiles (an entry
compiled for a described chip cannot be read back without one).
"""

import contextlib
import dataclasses
import functools
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs, kernels
from repro.kernels import dbuf_copy, flash_attention, memcpy, \
    paged_attention, pchase, rmsnorm, strided
from repro.models import transformer as T

INTERNVL2 = configs.get_config("internvl2-2b")
GRANITE = configs.get_config("granite-8b")
HBM_BYTES = 16 * 2**30
_PAGE_LEN = 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


_HEADS, _KV_HEADS, _HEAD_DIM = (INTERNVL2.num_heads, INTERNVL2.num_kv_heads,
                                INTERNVL2.head_dim)

#: name -> (kernel called with interpret=False, argument shapes and dtypes)
KERNELS = {
    "memcpy": (functools.partial(memcpy.memcpy, block_rows=256),
               [((4096, 512), jnp.float32)]),
    "rmsnorm": (rmsnorm.rmsnorm,
                [((4096, INTERNVL2.d_model), jnp.bfloat16),
                 ((INTERNVL2.d_model,), jnp.bfloat16)]),
    "dbuf_copy": (functools.partial(dbuf_copy.dbuf_copy, block_rows=256,
                                    num_buffers=2),
                  [((4096, 512), jnp.float32)]),
    # internvl2-2b's head geometry: 16 q / 8 kv heads of 128, S = 2048
    "flash_attention": (
        functools.partial(flash_attention.flash_attention,
                          num_q_heads=_HEADS, num_kv_heads=_KV_HEADS),
        [((_HEADS, 2048, _HEAD_DIM), jnp.bfloat16),
         ((_KV_HEADS, 2048, _HEAD_DIM), jnp.bfloat16),
         ((_KV_HEADS, 2048, _HEAD_DIM), jnp.bfloat16)]),
    # the decode step's paged attention at internvl2-2b's geometry: 8 rows
    # of 32 pages over the 24 layers' stacked 257-page pool
    "paged_decode_attention": (
        paged_attention.paged_decode_attention,
        [((8, _HEADS, _HEAD_DIM), jnp.bfloat16),
         ((24, 257, _PAGE_LEN, _KV_HEADS, _HEAD_DIM), jnp.bfloat16),
         ((24, 257, _PAGE_LEN, _KV_HEADS, _HEAD_DIM), jnp.bfloat16),
         ((), jnp.int32), ((8, 32), jnp.int32), ((8,), jnp.int32)]),
    "pchase": (functools.partial(pchase.pchase_trace, iterations=4096),
               [((1 << 20,), jnp.int32)]),
    "strided": (functools.partial(strided.strided_gather, stride=6),
                [((512, 128), jnp.float32)]),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_with_mosaic(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [_spec(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_interpret_default_follows_backend(monkeypatch):
    assert kernels.resolve_interpret(None) is (
        jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels.resolve_interpret(None) is False
    assert kernels.resolve_interpret(True) is True


def _paged_step(step, one_chip, *, model=INTERNVL2, layers=2, slots=8,
                num_pages=257):
    """``model`` (internvl2-2b) at its published widths (by default 2 of
    its layers, for test time) in the rehearsed pool geometry: 8 slots of
    4096 tokens, pages of 128, 257 pages.  The step is jitted as
    PagedServeEngine jits it, with the pool donated, and traced as on the
    chip (the default backend reads as a TPU, so decode attention takes
    the paged kernel); returns it compiled."""
    cfg = dataclasses.replace(model, num_layers=layers)
    pages_per_seq, page_len = 4096 // _PAGE_LEN, _PAGE_LEN

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                            tree)

    params = on_chip(jax.eval_shape(functools.partial(T.init_params, cfg),
                                    jax.random.key(0)))
    cache = on_chip(_pool(cfg, num_pages, slots))
    batch, s = (slots, 1) if step == "decode" else (1, page_len)

    def i32(*shape):
        return _spec(shape, jnp.int32, one_chip)

    def step_fn(p, c, t, st, tab, sl, sq):
        return T.paged_step(p, cfg, c, t, st, tab, sl, sq)

    seq_lens = None if step == "decode" else i32(batch)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return jax.jit(step_fn, donate_argnums=1).lower(
            params, cache, i32(batch, s), i32(batch),
            i32(batch, pages_per_seq), i32(batch), seq_lens).compile()


def _pool(cfg, num_pages, slots):
    """Shapes and dtypes of the paged pool."""
    return jax.eval_shape(lambda: T.init_paged_cache(cfg, num_pages,
                                                     _PAGE_LEN, slots))


def _used_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0, "the pool is not donated"
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_paged_step_fits_one_chip(step, one_chip):
    used = _used_bytes(_paged_step(step, one_chip))
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB"


#: an HLO instruction's result shape and opcode, for the ops that move a
#: whole buffer: ``%copy.81 = bf16[2,257,128,8,128]{...} copy(``
_MOVE_OP = re.compile(r"= [a-z0-9]+\[([\d,]*)\]\S* "
                      r"(copy|copy-done|dynamic-slice|dynamic-update-slice)\(")


def _squeeze(shape) -> tuple:
    return tuple(d for d in shape if d != 1)


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_paged_step_updates_pool_in_place(step, one_chip):
    """The pool rides the layer scan's carry: no temp buffer of a pool
    leaf's size, and no copy, slice or slice update of a whole pool leaf,
    stacked or one layer's.  Only the scatter touches the pool."""
    compiled = _paged_step(step, one_chip)
    leaf = jax.tree.leaves(_pool(dataclasses.replace(INTERNVL2,
                                                     num_layers=2), 257, 8))[0]
    leaf_bytes = leaf.size * leaf.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < leaf_bytes / 16, f"temp {temp / 2**30:.3f} GiB"
    pool_shapes = {_squeeze(leaf.shape), _squeeze(leaf.shape[1:])}
    moves = [m.group(0) for m in _MOVE_OP.finditer(compiled.as_text())
             if _squeeze(int(d) for d in m.group(1).split(",") if d)
             in pool_shapes]
    assert not moves, moves


def test_sixteen_slot_decode_step_fits_one_chip(one_chip):
    """All 24 layers of internvl2-2b decoding 16 slots of 4096 tokens
    over a pool of 513 pages (twice the 8-slot pool) compile for one chip
    and fit its memory."""
    used = _used_bytes(_paged_step("decode", one_chip, layers=24, slots=16,
                                   num_pages=513))
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB"


#: headers of the HLO text's debug tables, whose rows start with an id
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


def _strip_metadata(hlo: str) -> str:
    """The HLO text without op metadata and the source tables it points
    into, its instructions and computations renamed in order of first
    appearance (the lowering numbers names by what it met before, scope
    names included): what is left is the program."""
    lines = [ln for ln in hlo.splitlines()
             if ln not in _DEBUG_TABLES and not re.match(r"\d+ ", ln)]
    text = re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))
    names: dict = {}
    return re.sub(r"%([\w.\-]+)",
                  lambda m: "%" + names.setdefault(m.group(1),
                                                   f"n{len(names)}"), text)


#: the scopes of each step: decode attention reads the pool in the paged
#: kernel (scope ``attention``), the chunk gathers its pages first
_SCOPES = {"decode": ("kv_write", "attn_proj", "attention", "mlp", "head"),
           "prefill_chunk": ("kv_write", "kv_gather", "attn_proj",
                             "attention", "mlp", "head")}


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_named_scopes_change_op_metadata_only(step, one_chip, monkeypatch):
    """The step's parts carry their scope in ``op_name``; without the
    scopes the chip's compiler makes the same program, op for op."""
    scoped = _paged_step(step, one_chip).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _paged_step(step, one_chip).as_text()
    for scope in _SCOPES[step]:
        assert f"/{scope}/" in scoped, scope
        assert f"/{scope}/" not in plain, scope
    if step == "decode":
        assert "/kv_gather/" not in scoped
    assert _strip_metadata(scoped) == _strip_metadata(plain)


def _reserved_gib(compiled) -> float:
    """Arguments + temp of a compiled step, as the benchmark's
    ``step_hbm_gib`` reads them."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 2**30


@pytest.fixture(scope="module")
def internvl2_decode(one_chip):
    """All 24 layers of internvl2-2b's decode step, compiled once."""
    return _paged_step("decode", one_chip, layers=24)


def test_internvl2_decode_step_reservation_is_unchanged(internvl2_decode):
    """All 24 layers of internvl2-2b, which has no biases: the decode step
    reserves what it did before the bias flags existed."""
    assert round(_reserved_gib(internvl2_decode), 3) == 6.531


#: the benchmark cell's cut of granite-8b: 18 of its 36 layers
GRANITE_LAYERS = 18


@pytest.fixture(scope="module")
def granite_steps(one_chip):
    """The granite-8b cell's decode and chunk steps, compiled once."""
    return {step: _paged_step(step, one_chip, model=GRANITE,
                              layers=GRANITE_LAYERS)
            for step in ("decode", "prefill_chunk")}


def test_granite_decode_step_fits_one_chip(granite_steps):
    """Biased, tied granite-8b at the cell's sizes: 4,128,120,832 bf16
    parameters (7.690 GiB) and the 257-page pool (2.259 GiB) are the
    arguments; the step needs under a MiB of temp beside them."""
    compiled = granite_steps["decode"]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**20
    assert round(_reserved_gib(compiled), 3) == 9.949
    assert _used_bytes(compiled) < HBM_BYTES


#: a result shape in the HLO text: ``%x = f32[49152,4096]{...} opcode(``
_RESULT = re.compile(r"^\s*(?:ROOT )?%\S+ = [a-z0-9]+\[([\d,]*)\]\S* "
                     r"([a-z\-]+)\(")
#: a computation's header line, and a fusion's body it names
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) ")
_CALLS = re.compile(r" fusion\(.*calls=%([\w.\-]+)")
#: results that name a buffer and copy nothing
_VIEWS = {"parameter", "get-tuple-element", "bitcast"}


def _materialized(hlo: str):
    """(result shape, opcode, line) of every instruction that writes a
    buffer of its own: those outside fusion bodies, other than views."""
    fused = set(_CALLS.findall(hlo))
    out, inside = [], None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head and line.rstrip().endswith("{"):
            inside = head.group(1)
            continue
        m = _RESULT.match(line)
        if m and inside not in fused and m.group(2) not in _VIEWS:
            shape = tuple(int(d) for d in m.group(1).split(",") if d)
            out.append((shape, m.group(2), line.strip()[:160]))
    return out


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_tied_head_reads_the_table_in_place(step, granite_steps):
    """No op of the step writes a buffer of the embedding table's shape,
    transposed or not, in any dtype: the embedding lookup and the tied
    head read the (49152, 4096) table where it lies."""
    ops = _materialized(granite_steps[step].as_text())
    assert ops, "no instruction parsed"
    table = {(GRANITE.vocab_size, GRANITE.d_model),
             (GRANITE.d_model, GRANITE.vocab_size)}
    copies = [op for op in ops if op[0] in table]
    assert not copies, copies


def test_table_check_sees_a_copy(one_chip):
    """The check above finds a transposed float32 copy of a table where
    a step does make one."""
    table = _spec((GRANITE.vocab_size, 512), jnp.bfloat16, one_chip)
    x = _spec((8, 512), jnp.float32, one_chip)

    def copies(t, x):
        tt = t.T.astype(jnp.float32)
        return x @ tt, tt

    hlo = jax.jit(copies).lower(table, x).compile().as_text()
    assert any(op[0] == (512, GRANITE.vocab_size)
               for op in _materialized(hlo))


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_granite_step_names_its_parts(step, granite_steps):
    hlo = granite_steps[step].as_text()
    for scope in ("attn_proj", "mlp", "head"):
        assert f"/{scope}/" in hlo, scope


def _result_shapes(hlo: str) -> set:
    """The result shape of every instruction, inside fusions too, without
    its unit dimensions."""
    return {_squeeze(int(d) for d in m.group(1).split(",") if d)
            for m in map(_RESULT.match, hlo.splitlines()) if m}


def _gathered(model, batch: int) -> set:
    """A gathered table of ``batch`` rows of 32 pages, flat or paged,
    without unit dimensions."""
    return {_squeeze((batch, 32 * _PAGE_LEN, model.num_kv_heads,
                      model.head_dim)),
            _squeeze((batch, 32, _PAGE_LEN, model.num_kv_heads,
                      model.head_dim))}


@pytest.mark.parametrize("model", ["internvl2-2b", "granite-8b"])
def test_decode_step_attends_in_the_paged_kernel(model, internvl2_decode,
                                                 granite_steps):
    """Both benchmark models' decode steps (24 and 18 layers) run the
    paged kernel, compiled by Mosaic, once in the layer scan's body; no op
    of the step holds a gathered table of every row's pages."""
    cfg, hlo = ((INTERNVL2, internvl2_decode.as_text())
                if model == "internvl2-2b"
                else (GRANITE, granite_steps["decode"].as_text()))
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and "paged_decode_attention" in calls[0], calls
    assert not _result_shapes(hlo) & _gathered(cfg, 8)


def test_chunk_step_still_gathers(granite_steps):
    """The control: a prefill chunk gathers its row's whole table (and so
    the check above sees a gathered table where a step makes one)."""
    hlo = granite_steps["prefill_chunk"].as_text()
    assert "tpu_custom_call" not in hlo
    assert _result_shapes(hlo) & _gathered(GRANITE, 1)
