"""The main path's device programs compile for a TPU v5e that is described,
not attached: the TPU compiler is installed in the sandbox, so what it would
refuse on the chip it refuses here, at the sizes the chip serves.

Nothing runs — a passing compile says nothing about results or speed
(`python chip_smoke.py` on the chip does).  The topology is described inside
a fixture: only one process may load libtpu, and under pytest-xdist every
worker imports this file, so nothing here may touch it at import time.  All
chip compiles stay in this one file for the same reason.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np
import pytest

N_ONE_CHIP = 262_144      # chip_smoke.py's resident corpus
N_FOUR_CHIPS = 1_048_576  # its --chips 4 corpus, 262,144 rows per chip
DIMS = 1024


@pytest.fixture(scope="module")
def topo():
    """v5e:2x2 described for the compiler, with the persistent compile
    cache off: an entry compiled for a described chip is written but can
    never be read back here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices), ("data",))


@pytest.fixture
def dispatch_as_on_tpu(monkeypatch):
    """The dispatchers ask ``_on_tpu()``, which sees the CPU backend here;
    steer them to the branch a TPU process takes."""
    from nornicdb_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params_on(init, cfg, sharding):
    """Shapes of ``init(cfg, key)`` placed on ``sharding`` — no array is
    made (a described device cannot hold one)."""
    import jax

    shapes = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    return jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, sharding), shapes)


# Q classes: every vector search goes through the coalescing dispatcher,
# and the corpus scans a block of B queries at query_class(B): the grid
# below is all a DeviceCorpus is ever handed (batch_max = 256), the lone
# /nornicdb/search query included (class 8).
# Capacity doubles on growth: the 64 documents chip_smoke.py embeds after
# the bulk load push the resident buffer to 2 x 262,144 rows.
Q_GRID = (8, 16, 32, 64, 128, 256)


def test_q_grid_is_what_the_dispatcher_emits():
    from nornicdb_tpu.ops.similarity import query_class, query_classes
    from nornicdb_tpu.search.service import SearchConfig

    batch_max = SearchConfig().batch_max
    assert query_classes(batch_max) == Q_GRID
    assert {query_class(b) for b in range(1, batch_max + 1)} == set(Q_GRID)


@pytest.mark.parametrize("n,q", [(N_ONE_CHIP, q) for q in Q_GRID] + [
    (2 * N_ONE_CHIP, Q_GRID[0]), (2 * N_ONE_CHIP, Q_GRID[-1]),
])
@pytest.mark.parametrize("k", [10, 100])
def test_streaming_topk_one_chip(one_chip, dispatch_as_on_tpu, n, q, k):
    """DeviceCorpus.search's dispatch over the f32 corpus the service
    keeps resident: the bf16 streaming kernel must be what it selects."""
    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.ops.similarity import topk_backend

    compiled = jax.jit(
        lambda qs, c, v: topk_backend(qs, c, v, k)
    ).lower(
        _sds((q, DIMS), jnp.float32, one_chip),
        _sds((n, DIMS), jnp.float32, one_chip),
        _sds((n,), jnp.bool_, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("q", [Q_GRID[0], Q_GRID[-1]])
@pytest.mark.parametrize("k", [10, 100])
def test_streaming_topk_int8_one_chip(one_chip, dispatch_as_on_tpu, q, k):
    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.ops.similarity import topk_backend_int8

    compiled = jax.jit(
        lambda qs, c8, sc, v: topk_backend_int8(qs, c8, sc, v, k)
    ).lower(
        _sds((q, DIMS), jnp.float32, one_chip),
        _sds((N_ONE_CHIP, DIMS), jnp.int8, one_chip),
        _sds((N_ONE_CHIP,), jnp.float32, one_chip),
        _sds((N_ONE_CHIP,), jnp.bool_, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quantized,q,k", [
    (False, Q_GRID[0], 10), (False, Q_GRID[0], 100),
    (False, Q_GRID[-1], 10), (False, Q_GRID[-1], 100),
    (False, 16, 100), (True, 16, 100),
], ids=["f32-q8-k10", "f32-q8-k100", "f32-q256-k10", "f32-q256-k100",
        "f32-q16-k100", "int8-q16-k100"])
def test_sharded_search_four_chips(mesh4, dispatch_as_on_tpu, quantized, q, k):
    """ShardedCorpus.search's one program over a 4-device mesh — per-shard
    streaming kernel, all-gather merge — at the shape classes it pads a
    batch to: the query grid's ends and a 16-query batch (k_prog/local_k
    pow2 of k, at least 8; int8 oversamples rescore_factor=4 x k)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from nornicdb_tpu.ops.ivf import _next_pow2
    from nornicdb_tpu.parallel import sharded_index

    rows = NamedSharding(mesh4, P("data", None))
    vec = NamedSharding(mesh4, P("data"))
    rep = NamedSharding(mesh4, P())
    queries = _sds((q, DIMS), jnp.float32, rep)
    valid = _sds((N_FOUR_CHIPS,), jnp.bool_, vec)
    if quantized:
        k_dev = _next_pow2(max(4 * k, 8))
        lowered = sharded_index._sharded_search_int8.lower(
            queries,
            _sds((N_FOUR_CHIPS, DIMS), jnp.int8, rows),
            _sds((N_FOUR_CHIPS,), jnp.float32, vec),
            valid, k_dev, k_dev, "data", mesh4,
        )
    else:
        k_prog = _next_pow2(max(k, 8))
        lowered = sharded_index._sharded_search.lower(
            queries,
            _sds((N_FOUR_CHIPS, DIMS), jnp.float32, rows),
            valid, k_prog, k_prog, "data", mesh4,
        )
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text
    # one shard's rows per device, not the whole corpus on each
    per_device = compiled.memory_analysis().argument_size_in_bytes
    itemsize = 1 if quantized else 4
    assert per_device < 1.1 * N_FOUR_CHIPS * DIMS * itemsize / 4 + (64 << 20)


def test_bge_m3_forward_packed_full_width(one_chip):
    """The embed path's program at bge-m3's published widths (1024 h, 16
    heads, vocab 250,002, bf16), depth cut to 2 layers, for the (8, 512, 8)
    pack class chip_smoke.py's long documents fill."""
    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.models import bge_m3

    cfg = dataclasses.replace(bge_m3.BGE_M3, layers=2)
    grid = _sds((8, 512), jnp.int32, one_chip)
    cls = _sds((8,), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, ids, seg, pos, cr, cc: bge_m3.forward_packed(
            p, cfg, ids, seg, pos, cr, cc)
    ).lower(
        _params_on(bge_m3.init_params, cfg, one_chip),
        grid, grid, grid, cls, cls,
    ).compile()
    out, = jax.tree.leaves(compiled.out_info)
    assert out.shape == (8, 1024)


def _pool_gathers_are_flat(text: str, page: int, row: int) -> None:
    """Every gather of whole pages (slices of ``page`` slots of ``row``
    values) takes them by ONE index, the flat page number
    (``models/kv_walk.py``): the one-dimensional gather the chip runs at
    four times the rate of the gather with an index a pool axis (PERF.md
    section 6, PRs 31 and 38), and there is such a gather."""
    import re

    maps = [re.search(r"start_index_map=\{([0-9,]*)\}", line).group(1)
            for line in text.splitlines()
            if " gather(" in line
            and re.search(r"slice_sizes=\{[0-9,]*\b%d,%d\}" % (page, row), line)]
    assert maps and set(maps) == {"0"}, maps


# (lanes, table pages, pool pages, flat rows, chunk width, ceiling on
# cost_analysis()'s bytes accessed or None)
_QWEN_STEP = {
    "decode": (10, 16, 129, 8, 1, None),
    "prefill-chunk": (10, 16, 129, 64, 64, None),
    "cell-decode": (18, 512, 8193, 16, 1, 0.924e9),
    "cell-prefill-chunk": (18, 512, 8193, 64, 64, 1.03e9),
}


@pytest.mark.parametrize("case", list(_QWEN_STEP))
def test_ragged_fused_step_qwen_widths(one_chip, case):
    """The generation step at Qwen2.5-0.5B widths (896 h, 14/2 heads,
    vocab 151,936), 2 layers, page 16, with the attention implementation
    the engine dispatches on a TPU: the XLA walk over live lengths
    (``models/kv_walk.py``; the ragged Pallas kernel does not lower —
    docs/generation.md).  At the default engine geometry (8 lanes + chunk +
    dump, 16-page tables, 129-page pool) and at the benchmark cell's (16
    lanes + chunk + dump, 512-page tables, 8,193-page pool), where what the
    step moves is held too: the pool goes in and out donated, in ONE
    row-major layout and with no pool-sized copy (a 64-wide row made the
    compiler put another axis minor and copy the whole pool there and back
    every step), no f32 array of the gathered cache's extent exists (the
    copy ``repeat_kv`` made: 528 MB a layer), each attention block is a
    ``while`` that gathers a block of pages by FLAT page number (every
    gather of pages has ``start_index_map={0}``; the decode block's walk is
    two: the run of blocks every live lane's table begins with gathered
    WITHOUT a lane axis, ``bf16[128,16,128]``, from the first live lane's
    row, then the per-lane turns, ``bf16[17,128,16,128]``: PR 40), no
    layer's K or V is
    sliced out of the pool (``bf16[8193,16,128]``: 48 copies of 33.6 MB a
    step until PR 38) and no lane's whole table gathered, and the bytes
    accessed stay under what this program read when it was written, 0.770
    / 0.858 GB, plus a fifth (its predecessors read 1.470 / 2.057 and 9.98
    / 11.32 GB: PERF.md section 6).  The step compiled is the one the
    engine serves: it takes ``prev``, the previous step's ids and counts."""
    import re

    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.models import qwen2
    from nornicdb_tpu.ragged import pack_ragged_meta

    cfg = dataclasses.replace(qwen2.QWEN25_05B, layers=2)
    lmax, w, pages, f, tq, bytes_ceiling = _QWEN_STEP[case]
    meta, _ = pack_ragged_meta(lmax, w, f)
    pool = jax.eval_shape(
        lambda: qwen2.init_kv_pages(cfg, pages, 16)).shape  # nothing made
    compiled = qwen2.ragged_fused_step.lower(
        _params_on(qwen2.init_params, cfg, one_chip), cfg,
        _sds(meta.shape, jnp.int32, one_chip),
        _sds(pool, jnp.bfloat16, one_chip),
        lmax=lmax, w=w, tq=tq,
        # the served variant: the ids and the step's three counts
        prev=_sds((lmax + len(qwen2.STEP_COUNTERS),), jnp.int32, one_chip),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert pool[-1] == 128  # a row is one whole lane tile
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= int(np.prod(pool)) * 2  # donated
    shape = "bf16[%s]" % ",".join(map(str, pool))
    layouts = set(re.findall(re.escape(shape) + r"\{([0-9,]+)", text))
    assert layouts == {"4,3,2,1,0"}, layouts
    assert not re.search(re.escape(shape) + r"\S* copy\(", text)
    gathered = r"f32\[%d,%d,(%d|%d,%d),64\]" % (
        lmax, w * 16, cfg.heads, cfg.kv_heads, cfg.heads // cfg.kv_heads)
    assert not re.search(gathered, text)
    _pool_gathers_are_flat(text, 16, 128)
    if bytes_ceiling is not None:
        assert " while(" in text
        # a decode-only step has no one-lane chunk block: its gather of a
        # block WITHOUT a lane axis is the shared run's, beside the
        # per-lane one (in the chunk step the chunk block's reads alike)
        gathers = set(re.findall(r"= (bf16\[[0-9,]+\])\S* gather\(", text))
        assert "bf16[128,16,128]" in gathers, gathers
        assert gathers & {"bf16[%d,128,16,128]" % (lmax - 1),
                          "bf16[%d,16,128]" % ((lmax - 1) * 128)}, gathers
        assert "bf16[%d,16,128]" % pages not in text     # a layer's K or V
        for whole in ("[%d,16,128]" % (lmax * w), "[%d,%d,16,128]" % (lmax, w),
                      "[%d,16,128]" % ((lmax - 1) * w),
                      "[%d,%d,16,128]" % (lmax - 1, w)):
            assert whole not in text, whole              # a whole table
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        assert cost["bytes accessed"] < bytes_ceiling


@pytest.mark.parametrize("f,tq", [(16, 1), (64, 64)],
                         ids=["decode", "prefill-chunk"])
def test_mla_moe_fused_step_at_the_benchmark_cut(one_chip, f, tq):
    """The DeepSeek-V2 step at the benchmark's cut (published widths, 1 + 4
    layers, 20 held experts, 12,800-row head) and the cell's engine geometry
    (16 lanes + chunk + dump, page 16, 512-page tables, 8,193-page pool):
    it fits the chip beside the deployment's 5.43 GB, the latent pool goes
    in and out in ONE row-major layout, no pool-sized copy is left in
    the step (a 576-wide row makes the compiler put the pages axis minor
    and copy the whole pool to row-major and back: PERF.md section 5), and
    no gather of every lane's whole table either."""
    import re

    import jax.numpy as jnp

    from nornicdb_tpu.ragged import pack_ragged_meta
    from nornicdb_tpu.models import deepseek_v2 as ds

    cfg = ds.DEEPSEEK_V2_EP8_5L
    lmax, w, pages, page = 18, 512, 8193, 16
    meta, _ = pack_ragged_meta(lmax, w, f)
    pool = (cfg.num_hidden_layers, pages, page, cfg.page_row_width)
    compiled = ds.fused_step.lower(
        _params_on(ds.init_params, cfg, one_chip), cfg,
        _sds(meta.shape, jnp.int32, one_chip),
        _sds(pool, jnp.bfloat16, one_chip), lmax=lmax, w=w, tq=tq,
        # the served variant: the ids and the family's six counts
        prev=_sds((lmax + len(ds.STEP_COUNTERS),), jnp.int32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= int(np.prod(pool)) * 2  # donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < (16 << 30) - 5_430_000_000
    text = compiled.as_text()
    shape = "bf16[%s]" % ",".join(map(str, pool))
    layouts = set(re.findall(re.escape(shape) + r"\{([0-9,]+)", text))
    assert layouts == {"3,2,1,0"}, layouts
    assert not re.search(re.escape(shape) + r"\S* copy\(", text)
    _no_whole_table_gather(text)


def _no_whole_table_gather(text: str) -> None:
    """The step walks its lanes' tables a block of pages at a time
    (``models/mla.py``, PR 36): nothing of the extent of every page of
    every decode-block lane (17 lanes x 512 pages) is left, and what a
    turn gathers is one block of each lane's pages."""
    from nornicdb_tpu.models import mla

    assert "[8704,16,640]" not in text and "[17,512,16,640]" not in text
    assert f"bf16[{17 * mla.BLOCK_PAGES},16,640]" in text


@pytest.mark.parametrize("f,tq", [(16, 1), (64, 64)],
                         ids=["decode", "prefill-chunk"])
def test_scmoe_mla_fused_step_at_the_benchmark_cut(one_chip, f, tq):
    """LongCat-Flash's step at the benchmark's cut (published widths, 4
    double layers, 8 held experts, the 768-wide router, 16,384-row head)
    and the cell's engine geometry, as the DeepSeek-V2 case above: it fits
    the chip beside the deployment's 5.43 GB, and the latent pool, TWO pool
    layers a layer (``bf16[8,8193,16,640]``), goes in and out in ONE
    row-major layout with no pool-sized copy left in the step, and no
    gather of every lane's whole table either."""
    import re

    import jax.numpy as jnp

    from nornicdb_tpu.ragged import pack_ragged_meta
    from nornicdb_tpu.models import longcat_flash as lcf

    cfg = lcf.LONGCAT_FLASH_EP64_4L
    lmax, w, pages, page = 18, 512, 8193, 16
    meta, _ = pack_ragged_meta(lmax, w, f)
    pool = (cfg.attention_blocks, pages, page, cfg.page_row_width)
    assert pool == (8, 8193, 16, 640)
    compiled = lcf.fused_step.lower(
        _params_on(lcf.init_params, cfg, one_chip), cfg,
        _sds(meta.shape, jnp.int32, one_chip),
        _sds(pool, jnp.bfloat16, one_chip), lmax=lmax, w=w, tq=tq,
        # the served variant: the ids and the family's seven counts
        prev=_sds((lmax + len(lcf.STEP_COUNTERS),), jnp.int32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= int(np.prod(pool)) * 2  # donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < (16 << 30) - 5_430_000_000
    text = compiled.as_text()
    shape = "bf16[%s]" % ",".join(map(str, pool))
    layouts = set(re.findall(re.escape(shape) + r"\{([0-9,]+)", text))
    assert layouts == {"3,2,1,0"}, layouts
    assert not re.search(re.escape(shape) + r"\S* copy\(", text)
    _no_whole_table_gather(text)


@pytest.mark.parametrize("f,tq", [(16, 1), (64, 64), (128, 64)],
                         ids=["decode", "prefill-chunk", "widest"])
def test_parallel_moe_fused_step_at_the_benchmark_cut(one_chip, f, tq):
    """Command A+'s step at the benchmark's cut (published widths, one
    period of 4 layers, 8 held experts, the 128-wide router, 32,768-row
    tied head) and the cell's engine geometry by page kind (16 lanes +
    chunk + dump, page 16; full: 512-page tables, 8,193 pages, one layer;
    window: 261-page tables, 4,689 pages, three layers): it fits the chip
    beside the deployment's 5.43 GB, both K/V pools are donated and go in
    and out in ONE row-major layout with no pool-sized copy left in the
    step, and nothing of the extent of a lane's whole table is gathered: a
    turn of a walk takes one block of each lane's pages, by flat page
    number (the one-dimensional gather)."""
    import re

    import jax.numpy as jnp

    from nornicdb_tpu.ragged import pack_ragged_meta
    from nornicdb_tpu.models import cohere2_moe as cm
    from nornicdb_tpu.models import kv_walk

    cfg = cm.COMMAND_A_PLUS_EP16_4L
    lmax, w, pages, page = 18, (512, 261), (8193, 4689), 16
    meta, _ = pack_ragged_meta(lmax, w, f)
    pools = [(1, 2, 8193, page, 1024), (3, 2, 4689, page, 1024)]
    compiled = cm.fused_step.lower(
        _params_on(cm.init_params, cfg, one_chip), cfg,
        _sds(meta.shape, jnp.int32, one_chip),
        tuple(_sds(p, jnp.bfloat16, one_chip) for p in pools),
        lmax=lmax, w=w, tq=tq,
        # the served variant: the ids and the family's nine counts
        prev=_sds((lmax + len(cm.STEP_COUNTERS),), jnp.int32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        int(np.prod(p)) * 2 for p in pools)  # both donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < (16 << 30) - 5_430_000_000
    text = compiled.as_text()
    for pool in pools:
        shape = "bf16[%s]" % ",".join(map(str, pool))
        layouts = set(re.findall(re.escape(shape) + r"\{([0-9,]+)", text))
        assert layouts == {"4,3,2,1,0"}, (shape, layouts)
        assert not re.search(re.escape(shape) + r"\S* copy\(", text)
    for whole in ("[17,512,16,1024]", "[8704,16,1024]", "[17,261,16,1024]",
                  "[4437,16,1024]"):
        assert whole not in text, whole
    block = kv_walk.block_pages(_sds(pools[0], jnp.bfloat16, one_chip), 512)
    assert block == 32  # Command A+'s 32 KB pages keep their measured 32
    assert f"bf16[{17 * block},16,1024]" in text \
        or f"bf16[17,{block},16,1024]" in text
    # and the full kind's shared run, a block WITHOUT a lane axis (in a step
    # with a chunk the one-lane chunk block's gathers read alike)
    assert re.search(r"bf16\[%d,16,1024\]\S* gather\(" % block, text)
    _pool_gathers_are_flat(text, page, 1024)


@pytest.mark.parametrize("f,tq", [(16, 1), (128, 64)],
                         ids=["decode", "widest"])
def test_hybrid_fused_step_at_the_benchmark_cut(one_chip, f, tq):
    """Nemotron 3 Nano's step at the benchmark's cut (published widths, the
    first 27 layers: 12 Mamba-2, 11 expert, 4 attention; 16 held experts,
    the 128-wide router, a 16,384-row head) and the cell's engine geometry
    (16 lanes + chunk + dump, page 16, 512-page tables over 8,193 K/V
    pages, 82 state slots): it fits the chip beside the deployment's 5.43
    GB; the K/V pool AND both halves of the state pool are donated, and the
    2.06 GB of float32 SSM state goes in and out in one row-major layout
    with no pool-sized copy left in the step (twelve layers each gather 17
    lanes' slots and scatter them back in place)."""
    import re

    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.ragged import pack_ragged_meta
    from nornicdb_tpu.models import nemotron_h as nh

    cfg = nh.NEMOTRON_3_NANO_EP8_27L
    lmax, w = 18, (512, 1)
    meta, _ = pack_ragged_meta(lmax, w, f)
    pools = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: nh.init_pages(cfg, (8193, 82), 16)))
    compiled = nh.fused_step.lower(
        _params_on(nh.init_params, cfg, one_chip), cfg,
        _sds(meta.shape, jnp.int32, one_chip), pools, lmax=lmax, w=w, tq=tq,
        # the served variant: the ids and the family's eight counts
        prev=_sds((lmax + len(nh.STEP_COUNTERS),), jnp.int32, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    held = sum(int(np.prod(p.shape)) * p.dtype.itemsize
               for p in jax.tree.leaves(pools))
    assert held == 536_936_448 + 2_099_871_744
    assert mem.alias_size_in_bytes >= held  # every pool donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < (16 << 30) - 5_430_000_000
    text = compiled.as_text()
    ssm = "f32[12,82,64,64,128]"
    layouts = set(re.findall(re.escape(ssm) + r"\{([0-9,]+)", text))
    assert layouts == {"4,3,2,1,0"}, layouts
    assert not re.search(re.escape(ssm) + r"\S* copy\(", text)


def test_hybrid_decode_block_reads_a_lanes_state_once(one_chip):
    """The decode-only class of the step above: on a TPU the decode block's
    recurrence is one Pallas kernel a Mamba layer over the pool's own slots
    (``nemotron_h.step_slots_in_place``; ``lax.platform_dependent`` takes it
    when the step is lowered for the chip, as here), so no gathered copy of
    the 16 lanes' states exists in the step (the gather, the update and the
    scatter were three passes of 33.5 MB in and out a layer: PERF.md
    section 6, PR 43), and the pool still goes in and out aliased."""
    import jax
    import jax.numpy as jnp

    from nornicdb_tpu.ragged import pack_ragged_meta
    from nornicdb_tpu.models import nemotron_h as nh

    cfg = nh.NEMOTRON_3_NANO_EP8_27L
    lmax, w = 18, (512, 1)
    meta, _ = pack_ragged_meta(lmax, w, 16)
    pools = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: nh.init_pages(cfg, (8193, 82), 16)))
    compiled = nh.fused_step.lower(
        _params_on(nh.init_params, cfg, one_chip), cfg,
        _sds(meta.shape, jnp.int32, one_chip), pools, lmax=lmax, w=w, tq=1,
        prev=_sds((lmax + len(nh.STEP_COUNTERS),), jnp.int32, one_chip),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') \
        == len(cfg.layers_of(nh.MAMBA))
    assert "f32[16,64,64,128]" not in text  # the lanes' states, gathered
    held = sum(int(np.prod(p.shape)) * p.dtype.itemsize
               for p in jax.tree.leaves(pools))
    assert compiled.memory_analysis().alias_size_in_bytes >= held
