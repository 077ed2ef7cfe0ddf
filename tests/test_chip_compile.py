"""The programs `chip_smoke.py` launches, compiled for the chip without
the chip.

The TPU's compiler is installed with JAX and compiles for a v5e that is
DESCRIBED, not attached (the `on-chip-measurement` guide, section 2).
Nothing runs, so these say nothing about results or times — they say
the chip's compiler accepts each program at BASELINE config 5 shapes
(table 2^20 rows, batch 16,384, `artifacts/logreg_int8.npz`), which
interpret-mode tests and XLA:CPU cannot: Mosaic refuses misaligned
slices, oversized VMEM use and kernels it cannot partition.  The two
single-device step programs are also compiled at the benchmark's
`c4-syn-mix` shapes (2^26 rows, batch 2,048), and every step program is
searched for a temporary as long as the table: what the chip's compiler
makes of a column view that XLA:CPU would fuse away.

The topology is described only inside the module-scoped fixture below
(one process may hold the TPU library: see the guide for why that rules
out import time, `conftest.py`, autouse and child processes), and
everything built from it is built in a fixture or a test.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from flowsentryx_tpu import parallel as par
from flowsentryx_tpu.core import schema
from flowsentryx_tpu.core.config import BatchConfig, FsxConfig, TableConfig
from flowsentryx_tpu.models import get_model
from flowsentryx_tpu.models.registry import load_artifact
from flowsentryx_tpu.ops import fused, pallas_kernels
from flowsentryx_tpu.parallel import layout

CAPACITY = 1 << 20
BATCH = 16384
WORDS = schema.COMPACT_RECORD_WORDS


def _cfg(capacity, batch, **aging):
    return FsxConfig(table=TableConfig(capacity=capacity, salt=0x5EED5EED,
                                       **aging),
                     batch=BatchConfig(max_batch=batch))


CFG = _cfg(CAPACITY, BATCH)
#: [capacity, 12] f32 rows + the u32 key column, as the host counts them
#: (the chip pads the 12-wide minor dimension, so it aliases more).
TABLE_BYTES = CAPACITY * (schema.NUM_TABLE_COLS + 1) * 4


@pytest.fixture(scope="module")
def topo():
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it undescribed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to JAX's persistent
    # cache but cannot be read back without the chip: keep these out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices), ("ip",))


@pytest.fixture
def mosaic(monkeypatch):
    """`_interpret()` asks the default backend, which is the CPU here:
    steer it from the test so the kernels lower through Mosaic as they
    do on the chip."""
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)


@pytest.fixture(scope="module")
def served():
    """(classify_batch, quantizer kwargs, params) of the artifact the
    smoke serves."""
    params = load_artifact(CFG.model.name, "artifacts/logreg_int8.npz")
    return (get_model(CFG.model.name).classify_batch,
            schema.wire_quant_for(params), params)


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _state(params, key_sh, state_sh, rest_sh, capacity=CAPACITY):
    table = schema.IpTableState(
        key=jax.ShapeDtypeStruct((capacity,), jnp.uint32, sharding=key_sh),
        state=jax.ShapeDtypeStruct((capacity, schema.NUM_TABLE_COLS),
                                   jnp.float32, sharding=state_sh))
    stats = _abstract(jax.eval_shape(schema.make_stats), rest_sh)
    return table, stats, _abstract(params, rest_sh)


def _wire(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def test_table_summary_kernel_compiles_with_mosaic(one_chip, mosaic):
    """On EVERY serve run: Engine._build_report calls it."""
    col = jax.ShapeDtypeStruct((CAPACITY,), jnp.float32, sharding=one_chip)
    compiled = pallas_kernels._table_summary_device.lower(
        jax.ShapeDtypeStruct((CAPACITY,), jnp.uint32, sharding=one_chip),
        col, col,
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        30.0).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [2048, BATCH])
def test_score_int8_kernel_compiles_with_mosaic(one_chip, mosaic, served,
                                                batch):
    compiled = pallas_kernels.score_int8.lower(
        _abstract(served[2], one_chip),
        jax.ShapeDtypeStruct((batch, schema.NUM_FEATURES), jnp.float32,
                             sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: (table rows, batch, limit of the temporaries): BASELINE config 5 as
#: `chip_smoke.py` serves it, where the temporaries are the batch's own
#: (20 MB at 16,384 records), and the benchmark's `c4-syn-mix`
#: (`benchmark/configs/`), where the table is 4.56 GB and the limit is
#: one f32 column of it: the temporaries were 272 MB while the probe
#: took `table.last_seen`, and are 4 MB since it gathers (ISSUE 30);
#: and the benchmark's `c5-l34-1m` (ISSUE 32): that table under
#: 16,384-record batches, where the temporaries are the batch's again;
#: and the benchmark's `c6-spoof-churn` (ISSUE 39): `c5-l34-1m`'s shapes
#: with the aging sweep compiled in, a 2^17-row window a batch.  Lowered
#: for the chip the window is sliced out of the table and sliced back in
#: (`ops/fused.py::evict_idle_epoch`'s `tpu` form, ISSUE 40: read by
#: gather and freed by scatter it was 23.4 ms of a 30.8 ms step), which
#: has to leave the table in place as the step's own scatters do:
#: `_assert_the_sweep_is_two_slices`.
NO_AGING = {}
C6_AGING = {"evict_ttl_s": 12.0, "evict_every": 512}
STEP_SHAPES = [
    pytest.param(CAPACITY, BATCH, 32 << 20, NO_AGING, id="c5-smoke"),
    pytest.param(1 << 26, 2048, (1 << 26) * 4, NO_AGING, id="c4-benchmark"),
    pytest.param(1 << 26, BATCH, 32 << 20, NO_AGING, id="c5-benchmark"),
    pytest.param(1 << 26, BATCH, 64 << 20, C6_AGING, id="c6-benchmark")]


def _foreign_table_sized_results(text, capacity):
    """(opcode, dtype, dims) of every instruction of the compiled
    program whose result has `capacity` among its dimensions and is
    neither the key column nor the state matrix: a column, a copy, a
    transpose or a fusion output as long as the table."""
    own = {("u32", (capacity,)),
           ("f32", (capacity, schema.NUM_TABLE_COLS))}
    found = set()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m:
            continue
        for dtype, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]",
                                      m.group(1)):
            dims = tuple(int(d) for d in dims.split(",") if d)
            if capacity in dims and (dtype, dims) not in own:
                found.add((m.group(2), dtype, dims))
    return found


def _assert_in_place_and_no_table_sized_temporary(compiled, capacity,
                                                  temp_limit):
    mem = compiled.memory_analysis()
    # donation really aliases: the whole table updates in place
    assert (mem.alias_size_in_bytes
            >= capacity * (schema.NUM_TABLE_COLS + 1) * 4)
    # the step reads the table by gather and writes it by scatter: a
    # column view stages a `capacity`-long temporary every step
    assert _foreign_table_sized_results(compiled.as_text(), capacity) == set()
    assert mem.temp_size_in_bytes < temp_limit


def _passed_through(text, counter):
    """The compiled program hands ``stats.<counter>`` back as it came:
    the ENTRY's result tuple holds the parameter itself."""
    entry = text[text.index("ENTRY"):]
    root = next(ln for ln in entry.splitlines() if "ROOT" in ln)
    param = re.search(rf"%(stats_{counter}[\w.]*) = u32\[2\]\{{[^}}]*\}} "
                      r"parameter\(", entry)
    return bool(param) and f"%{param.group(1)}," in root


def _assert_the_sweep_is_two_slices(compiled, capacity):
    """Every instruction of the aging sweep (`op_name` under the
    `fsx.evict` scope): none is a scatter, a sort or a fusion over
    either, and the window goes back into the donated row matrix by a
    `dynamic-update-slice` whose result is the table itself."""
    text = compiled.as_text()
    computations = dict(re.findall(
        r"^(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.M | re.S))
    sweep = [ln for ln in text.splitlines()
             if re.search(r'op_name="[^"]*fsx\.evict', ln)]
    assert sweep
    state = f"f32[{capacity},{schema.NUM_TABLE_COLS}]"
    back_in_place = False
    for ln in sweep:
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", ln)
        if not m:
            continue
        result, opcode = m.groups()
        body = ln
        called = re.search(r"calls=%([\w.\-]+)", ln)
        if opcode == "fusion" and called:
            body += computations.get(called.group(1), "")
        assert not re.search(r"\b(scatter|sort)\(", body), ln[:200]
        if result.startswith(state) and "dynamic-update-slice(" in body:
            back_in_place = True
    # (that the result is the donated buffer and not a copy of it is
    # the callers' alias and temporary limits)
    assert back_in_place


@pytest.mark.parametrize("capacity,batch,temp_limit,aging", STEP_SHAPES)
def test_single_compact_step_compiles_and_aliases_the_table(
        one_chip, served, capacity, batch, temp_limit, aging):
    classify, quant, params = served
    step = fused.make_jitted_compact_step(_cfg(capacity, batch, **aging),
                                          classify, **quant)
    compiled = step.lower(
        *_state(params, one_chip, one_chip, one_chip, capacity),
        _wire((batch + 1, WORDS), one_chip)).compile()
    _assert_in_place_and_no_table_sized_temporary(compiled, capacity,
                                                  temp_limit)
    # the sharded step's counter: one device's program passes it on
    assert _passed_through(compiled.as_text(), "owner_rows")
    if aging:
        _assert_the_sweep_is_two_slices(compiled, capacity)


@pytest.mark.parametrize("capacity,batch,temp_limit,aging", STEP_SHAPES)
def test_top_mega_rung_compiles_and_aliases_the_table(
        one_chip, served, capacity, batch, temp_limit, aging):
    classify, quant, params = served
    top = max(fused.pow2_group_sizes(8))
    mega = fused.make_compact_megastep_family(
        _cfg(capacity, batch, **aging), classify, (top,), **quant)[top]
    compiled = mega.lower(
        *_state(params, one_chip, one_chip, one_chip, capacity),
        _wire((top, batch + 1, WORDS), one_chip)).compile()
    _assert_in_place_and_no_table_sized_temporary(compiled, capacity,
                                                  temp_limit)
    assert _passed_through(compiled.as_text(), "owner_rows")
    if aging:
        _assert_the_sweep_is_two_slices(compiled, capacity)


@pytest.mark.parametrize("top", [1, 8], ids=["single", "mega-8"])
def test_the_128_bit_step_compiles_and_aliases_the_table(one_chip, served,
                                                         top):
    """`c8-dual6-mix`'s shapes (`benchmark/configs/`): 2^26 rows keyed
    by 16 B (`TableConfig.key_bits` 128), 2,048-record batches of the
    7-word wire, a single step and the top mega rung.  The key
    `u32[capacity, 4]` and the rows update in place, and nothing else
    as long as the table is made."""
    classify, quant, params = served
    cap, b = 1 << 26, 2048
    cfg = dataclasses.replace(_cfg(cap, b), table=TableConfig(
        capacity=cap, salt=0x5EED5EED, key_bits=128))
    table = schema.IpTableState(
        key=jax.ShapeDtypeStruct((cap, 4), jnp.uint32, sharding=one_chip),
        state=jax.ShapeDtypeStruct((cap, schema.NUM_TABLE_COLS),
                                   jnp.float32, sharding=one_chip))
    stats = _abstract(jax.eval_shape(schema.make_stats), one_chip)
    words = schema.COMPACT28_RECORD_WORDS
    if top == 1:
        step = fused.make_jitted_compact_step(cfg, classify, **quant)
        wire = _wire((b + 1, words), one_chip)
    else:
        step = fused.make_compact_megastep_family(
            cfg, classify, (top,), **quant)[top]
        wire = _wire((top, b + 1, words), one_chip)
    compiled = step.lower(table, stats, _abstract(params, one_chip),
                          wire).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cap * (16 + schema.NUM_TABLE_COLS * 4)
    found = {f for f in _foreign_table_sized_results(compiled.as_text(), cap)
             if f[1:] != ("u32", (cap, 4))}
    assert found == set()
    assert mem.temp_size_in_bytes < cap * 4


def test_a_32_bit_step_passes_the_v6_counters_through(one_chip, served):
    """`GlobalStats.v6_records` and `.v6_blocks` are counted where the
    table keys by 128 bits; a 32-bit program hands them back as they
    came, so its graph is the one it was."""
    classify, quant, params = served
    step = fused.make_jitted_compact_step(CFG, classify, **quant)
    text = step.lower(*_state(params, one_chip, one_chip, one_chip),
                      _wire((BATCH + 1, WORDS), one_chip)).compile().as_text()
    assert _passed_through(text, "v6_records")
    assert _passed_through(text, "v6_blocks")


@pytest.mark.parametrize("aging", [NO_AGING, C6_AGING],
                         ids=["no-aging", "c6-aging"])
def test_sharded_step_compiles_for_four_chips(mesh4, served, aging):
    """`fsx serve --mesh 4`: rows sharded by parallel/layout.py, the
    wire replicated; the compiler puts in the designed collectives and
    each chip holds a quarter of the table.  With aging each chip
    sweeps a window of its own quarter, by the `tpu` form under
    `shard_map`: no collective more, the shard still in place."""
    classify, quant, params = served
    rep = NamedSharding(mesh4, P())
    step = par.make_sharded_compact_step(_cfg(CAPACITY, BATCH, **aging),
                                         classify, mesh4, **quant)
    compiled = step.lower(
        *_state(params, layout.sharding_for(mesh4, "table.key"),
                layout.sharding_for(mesh4, "table.state"), rep),
        _wire((BATCH + 1, WORDS), rep)).compile()
    text = compiled.as_text()
    assert text.count("all-to-all(") == 2  # flows out, verdicts back
    # `flow_step` probes its shard by gather too: no shard-long column
    assert _foreign_table_sized_results(text, CAPACITY // 4) == set()
    mem = compiled.memory_analysis()  # bytes on EACH device
    assert TABLE_BYTES // 4 <= mem.alias_size_in_bytes < TABLE_BYTES // 2
    if aging:
        _assert_the_sweep_is_two_slices(compiled, CAPACITY // 4)


def test_the_mesh_cells_owners_update_their_shards_in_place(mesh4, served):
    """`c5-l34-mesh4` at its own shapes (2^28 rows over four chips,
    65,536-record batches, the top mega rung): the owner side runs the
    flow core in three loops of 0 or 1 trips (`parallel/step.py::
    owner_flow_step`), each carrying the chip's shard.  The shard stays
    a loop carry and no conditional returns it, nothing shard-long is
    copied, the arguments stay aliased, and the temporaries stay the
    batch's own (20 MB; 17–30 MB before the rungs)."""
    classify, quant, params = served
    capacity, batch, top = 1 << 28, 1 << 16, max(fused.pow2_group_sizes(8))
    rep = NamedSharding(mesh4, P())
    cfg = dataclasses.replace(_cfg(capacity, batch), table=TableConfig(
        capacity=capacity, salt=0x5EED5EED, shards=4))
    mega = par.make_sharded_compact_megastep_family(
        cfg, classify, mesh4, (top,), **quant)[top]
    compiled = mega.lower(
        *_state(params, layout.sharding_for(mesh4, "table.key"),
                layout.sharding_for(mesh4, "table.state"), rep, capacity),
        _wire((top, batch + 1, WORDS), rep)).compile()
    text = compiled.as_text()
    shard = capacity // 4
    loops = [ln for ln in text.splitlines() if re.search(r" while\(", ln)]
    # the scan over the group, and the three rungs inside it
    assert len(loops) == 4
    assert all(f"u32[{shard}]" in ln and f"f32[{shard},12]" in ln
               for ln in loops)
    conds = [ln for ln in text.splitlines() if " conditional(" in ln]
    assert not [ln for ln in conds if f"[{shard}" in ln.split("=", 1)[1]
                .split(" conditional(")[0]]
    assert _foreign_table_sized_results(text, shard) == set()
    mem = compiled.memory_analysis()  # bytes on EACH device
    quarter = shard * (schema.NUM_TABLE_COLS + 1) * 4
    assert quarter <= mem.alias_size_in_bytes < 2 * quarter
    assert mem.temp_size_in_bytes < 64 << 20


def test_a_table_no_chip_holds_is_made_a_shard_a_chip(mesh4):
    """`c5-l34-mesh4`'s 2^28 rows are 18.3 GB as the chip lays them
    out: the empty table is made where it lies, 4.56 GB a chip and no
    temporary (made whole on one chip and then placed, it asked for
    16 GB of a chip's 14.75 free: my chip run, PR 42)."""
    capacity = 1 << 28
    mem = par.step.empty_table_fn(capacity, mesh4).lower().compile(
    ).memory_analysis()
    quarter = capacity // 4 * (schema.NUM_TABLE_COLS + 1) * 4
    assert quarter <= mem.output_size_in_bytes < 5 * 10**9
    assert mem.temp_size_in_bytes == 0


def test_sharded_table_summary_needs_the_xla_twin(mesh4, mosaic):
    """Why `table_summary` keeps a sharded table off the Pallas kernel:
    the chip's compiler will not partition a Mosaic kernel over a mesh,
    and the XLA twin compiles there with its cross-chip reductions."""
    rep = NamedSharding(mesh4, P())
    args = (
        jax.ShapeDtypeStruct((CAPACITY,), jnp.uint32,
                             sharding=layout.sharding_for(mesh4,
                                                          "table.key")),
        jax.ShapeDtypeStruct((CAPACITY, schema.NUM_TABLE_COLS), jnp.float32,
                             sharding=layout.sharding_for(mesh4,
                                                          "table.state")),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
    )

    def lowered(use_pallas):
        return jax.jit(functools.partial(
            pallas_kernels._table_summary, stale_s=30.0,
            use_pallas=use_pallas)).lower(*args)

    with pytest.raises(NotImplementedError, match="partitioned"):
        lowered(True).compile()
    assert "all-reduce(" in lowered(False).compile().as_text()


def test_the_saves_snapshot_takes_no_temporary_at_2_26_rows(one_chip):
    """The periodic save's snapshot (``checkpoint.snapshot``) at the
    benchmark's 2^26 rows: the key copied and the twelve columns split
    out, so that the chip holds the table and one copy of its bytes and
    no third table's worth; then each section cut into ``PIECE_BYTES``
    pieces as it is sent, by one small program a piece size."""
    from flowsentryx_tpu.engine import checkpoint as ckpt

    rows = 1 << 26
    key = jax.ShapeDtypeStruct((rows,), jnp.uint32, sharding=one_chip)
    state = jax.ShapeDtypeStruct((rows, schema.NUM_TABLE_COLS),
                                 jnp.float32, sharding=one_chip)
    mem = ckpt._snapshot_program().lower(
        key, state).compile().memory_analysis()
    # the sections, and the output tuple's table of their addresses
    table_bytes = rows * (schema.NUM_TABLE_COLS + 1) * 4
    assert 0 <= mem.output_size_in_bytes - table_bytes < 1 << 12
    assert mem.temp_size_in_bytes == 0
    piece_rows = ckpt.PIECE_BYTES // 4
    assert rows % piece_rows == 0 and rows // piece_rows > 1
    column = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    mem = ckpt._piece_program(piece_rows).lower(
        column, start).compile().memory_analysis()
    assert mem.output_size_in_bytes == ckpt.PIECE_BYTES
    assert mem.temp_size_in_bytes == 0
