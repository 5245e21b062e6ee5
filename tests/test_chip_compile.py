"""Ask the TPU v5e compiler about every Pallas kernel, without a chip.

The TPU compiler is installed next to JAX and compiles for a chip that is
described, not attached. Interpret-mode tests (every other kernel test in
this suite) cannot see what it refuses: block shapes that break the
(8, 128) tiling rule, slices it cannot align, too much VMEM. Each case
below lowers and compiles one `pallas_call` entry point of
`parallel/kernels.py` at the widths `chip_smoke.py` drives on the chip
(a 1,048,576-doc partition, 768-d vectors) — nothing runs, so this says
nothing about results or speed.

This is the ONLY file that describes a topology: the call loads libtpu,
which one process at a time may hold, so it lives in a module-scoped
fixture and runs after collection (never at import).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from elasticsearch_tpu.parallel import kernels as K

DOCS = 1 << 20                    # one smoke-sized partition
NSW = DOCS // K.SW                # 16 superwindows
DPC = NSW * K.N_CHUNKS            # 2048-doc chunks
N_COLIZABLE = 152                 # df >= COLD_DF terms of the smoke corpus
HP = ((N_COLIZABLE + 8 + 31) // 32) * 32   # TurboBM25.__init__ slot rounding
HPT = HP + 1                      # + the build scratch slot
KNN_DOCS = 100_000
KNN_NW = -(-KNN_DOCS // K.KNN_W)  # 49 windows
KNN_DIMSP = 768                   # KnnEngine pads dims to 128x; 768 already is


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip compile can be written to the persistent cache but
    not read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()
    # traces made here carry interpret=False; never let a CPU test reuse one
    jax.clear_caches()


@pytest.fixture
def chip(one_chip, no_compile_cache, monkeypatch):
    """Steer the kernels' backend probe to 'TPU' for this test only and
    hand back a ShapeDtypeStruct factory placed on the described chip."""
    monkeypatch.setattr(K, "_interpret", lambda: False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return sds


def _compiled(fn, *args, **static):
    exe = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in exe.as_text()
    return exe


def _cols(s):
    return s((DPC, HPT, K.CHUNK_ROWS, 128), jnp.int8)


@pytest.mark.parametrize("QC", [8, 256])
def test_sweep_rowmax(chip, QC):
    _compiled(K.sweep_rowmax,
              chip((QC, 1), jnp.float32), _cols(chip), _cols(chip),
              chip((2, QC, HPT), jnp.int8),
              chip((NSW * K.SW_ROWS, 128), jnp.float32), QC=QC, nsw=NSW)


@pytest.mark.parametrize("QC", [8, 256])
def test_sweep_rowmax_conj(chip, QC):
    _compiled(K.sweep_rowmax_conj,
              chip((QC, 1), jnp.float32), chip((QC, 1), jnp.int32),
              _cols(chip), _cols(chip), chip((2, QC, HPT), jnp.int8),
              chip((QC, HPT), jnp.int8),
              chip((NSW * K.SW_ROWS, 128), jnp.float32), QC=QC, nsw=NSW)


def test_pack_presence_bits(chip):
    # plain XLA (no pallas_call), but it builds the bitset sweep's input
    K.pack_presence_bits.lower(_cols(chip), _cols(chip)).compile()


def _bits(s):
    return s((HP + 2 + K.BITSET_COLD_ROWS, NSW * K.SW_WORD_ROWS, 128),
             jnp.uint32)


@pytest.mark.parametrize("n", [1, 64])
def test_bitset_rows_written_and_counted(chip, n):
    # plain XLA too: what keeps the bitsets' rows (the re-pack over the
    # column rows, the cold clauses' rows written from the host) and what
    # counts a conjunction's hits
    K.bitset_repack.lower(_bits(chip), _cols(chip), _cols(chip)).compile()
    K.bitset_write_rows.lower(
        _bits(chip), chip((n,), jnp.int32),
        chip((n, NSW * K.SW_WORD_ROWS, 128), jnp.uint32)).compile()
    K.mask_live_counts.lower(
        chip((8 * n, NSW * K.SW_WORD_ROWS, 128), jnp.uint32),
        chip((NSW * K.SW_WORD_ROWS, 128), jnp.uint32)).compile()


@pytest.mark.parametrize("QC", [8, 256])
def test_intersect_bitset(chip, QC):
    _compiled(K.intersect_bitset,
              chip((QC, K.BITSET_CLAUSES), jnp.int32),
              chip((QC, K.BITSET_NEGS), jnp.int32), _bits(chip),
              QC=QC, nsw=NSW)


@pytest.mark.parametrize("QC", [8, 256])
def test_sweep_rowmax_bitset(chip, QC):
    _compiled(K.sweep_rowmax_bitset,
              chip((QC, 1), jnp.float32), _cols(chip), _cols(chip),
              chip((2, QC, HPT), jnp.int8),
              chip((QC, NSW * K.SW_WORD_ROWS, 128), jnp.uint32),
              chip((NSW * K.SW_ROWS, 128), jnp.float32), QC=QC, nsw=NSW)


@pytest.mark.parametrize("Q", [8, 256])
def test_merge_topk(chip, Q):
    S, k = 4, 10
    _compiled(K.merge_topk, chip((Q, S * k), jnp.float32),
              chip((Q, S * k), jnp.int32), k=k)


@pytest.mark.parametrize("n_groups", [256, 32768])
def test_build_columns(chip, n_groups):
    tr = 200_000 + K.MAX_GROUP_ROWS       # posting rows + DMA padding
    g = chip((n_groups,), jnp.int32)
    _compiled(K.build_columns, g, g, g, g,
              chip((tr, 128), jnp.int32), chip((tr, 128), jnp.float32),
              _cols(chip), _cols(chip), n_groups=n_groups)


@pytest.mark.parametrize("docs", [DOCS, 393_216, 1 << 23],
                         ids=["smoke", "msmarco-segment", "doc-limit"])
@pytest.mark.parametrize("rung", [0, -1], ids=["smallest", "largest"])
def test_sparse_gather(chip, rung, docs):
    """The group gather at the first and the last rung of its ladder
    (`turbo._SPARSE_RUNGS`: the last one's four step rows are a quarter of
    the chip's scalar memory), over the smoke's partition, a benchmark
    segment's, and the most docs the sparse tier takes (the accumulator of
    a query is VMEM scratch: 64 KB a 16,384-doc tile, 32 MB there)."""
    from elasticsearch_tpu.parallel.turbo import (
        _SPARSE_DOC_LIMIT, _SPARSE_RUNGS,
    )

    assert docs <= _SPARSE_DOC_LIMIT
    n_steps, n_chunks = _SPARSE_RUNGS[rung]
    _compiled(K.sparse_gather, chip((4, n_steps), jnp.int32),
              chip((5040, K.SPARSE_GRAN // 128, 128), jnp.int32),
              n_chunks=n_chunks, n_tiles=docs // K.TILE)


@pytest.mark.parametrize("Q", [1, 16])
def test_agg_segment_counts(chip, Q):
    p = DOCS                              # one (doc, bucket) pair per doc
    nc = p // K.AGG_PAIR_GRAN
    _compiled(K.agg_segment_counts, chip((Q, DOCS), jnp.bool_),
              chip((2 * p + 2 * nc,), jnp.int32), p=p, n_segments=256)


def test_agg_two_level_counts(chip):
    pd = pm = DOCS
    nc = pd // K.AGG_PAIR_GRAN
    _compiled(K.agg_two_level_counts, chip((1, DOCS), jnp.bool_),
              chip((2 * pd + 2 * nc + 2 * pm + 2 * nc,), jnp.int32),
              pd=pd, pm=pm, n_segments=512)


@pytest.mark.parametrize("identity", [True, False],
                         ids=["identity", "gathered"])
@pytest.mark.parametrize("Q,fields", [(1, 1), (16, 2)])
def test_agg_filter_counts(chip, Q, fields, identity):
    """The filter + bucket route's reduction: the selection made from
    rank bounds on the device, at a lane's narrowest and widest width,
    over one and two filter columns, a minute layout's four tiles. The
    chunk axis' length is read on the device from the rows' chunk ranges
    (a grid bound that is an operand): this ONE program is every range's,
    there is no ladder of rungs to compile."""
    p = DOCS
    nc = p // K.AGG_PAIR_GRAN
    n = DOCS if not identity else DOCS - 5    # the pad to p is exercised
    _compiled(K.agg_filter_counts, chip((Q, fields, 2), jnp.int32),
              chip((Q, 2), jnp.int32),
              tuple(chip((n,), jnp.int32) for _ in range(fields)),
              chip((2 * p + 2 * nc,), jnp.int32), p=p,
              n_out=4 * K.AGG_SEG_TILE, identity=identity)


@pytest.mark.parametrize("similarity", ["cosine", "dot_product", "l2_norm"])
@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
@pytest.mark.parametrize("QC", [8, 128])
def test_knn_int8_window_topc(chip, QC, masked, similarity):
    args = [chip((QC, KNN_DIMSP), jnp.int8), chip((QC, 8), jnp.float32),
            chip((KNN_NW, KNN_DIMSP, K.KNN_W), jnp.int8),
            chip((KNN_NW, 4, K.KNN_W), jnp.float32),
            chip((KNN_NW, QC, 1), jnp.float32)]
    if masked:
        args.append(chip((KNN_NW, QC, K.KNN_W), jnp.int8))
    _compiled(K.knn_int8_window_topc, *args, similarity=similarity)


@pytest.mark.parametrize("Q", [1, 8])
def test_knn_scores_rounds_the_query_whatever_the_batch(chip, Q):
    """A single query's gemm is strength-reduced to an f32 multiply-reduce
    (no MXU); on the chip that program scored the unrounded query until
    ops.knn.bf16_operand rounded it with reduce_precision, which has to
    survive the TPU compiler in both program shapes."""
    from elasticsearch_tpu.ops.knn import knn_scores

    n = 12_500
    text = knn_scores.lower(
        chip((Q, 768), jnp.float32), chip((n, 768), jnp.bfloat16),
        chip((n,), jnp.float32), chip((n,), jnp.bool_),
        similarity="cosine").compile().as_text()
    assert "reduce-precision(" in text
    assert ("convolution(" in text) == (Q > 1)


# --------------------------------------------------------------------------
# the four-chip programs: one shard_map launch over a (1, 4) mesh built
# from the described topology's devices — what `chip_smoke.py --chips 4`
# dispatches (12 partitions of a 4-shard, 3-segment index, 3 per chip)
# --------------------------------------------------------------------------

SP = 12                           # partitions, padded to a mesh multiple
NSW4 = 2                          # a 1M/12-doc partition: 2 superwindows
HP4 = 64
K_ROWS = 33                       # turbo._GLOBAL_ROWS


@pytest.fixture(scope="module")
def mesh4(topo):
    from elasticsearch_tpu.parallel.spmd import make_mesh

    return make_mesh(devices=topo.devices, dp=1)


@pytest.fixture
def shard4(mesh4, no_compile_cache, monkeypatch):
    monkeypatch.setattr(K, "_interpret", lambda: False)
    sh = NamedSharding(mesh4, P("shard"))
    rep = NamedSharding(mesh4, P())

    def sds(shape, dtype, replicated=False):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=rep if replicated else sh)

    return sds


def _cols4(s):
    return s((SP, NSW4 * K.N_CHUNKS, HP4 + 1, K.CHUNK_ROWS, 128), jnp.int8)


@pytest.mark.parametrize("QC", [8, 256])
def test_fused_sweep_disj_four_chips(shard4, mesh4, QC):
    from elasticsearch_tpu.parallel.turbo import _fused_sweep_disj

    _compiled(_fused_sweep_disj,
              shard4((SP, QC, 1), jnp.float32), _cols4(shard4),
              _cols4(shard4), shard4((SP, 2, QC, HP4 + 1), jnp.int8),
              shard4((SP, NSW4 * K.SW_ROWS, 128), jnp.float32),
              mesh=mesh4, QC=QC, nsw=NSW4, n_rows=K_ROWS)


def test_fused_sweep_bitset_four_chips(shard4, mesh4):
    from elasticsearch_tpu.parallel.turbo import _fused_sweep_bitset

    QC = 8
    _compiled(_fused_sweep_bitset,
              shard4((SP, QC, 1), jnp.float32),
              shard4((SP, QC, K.BITSET_CLAUSES), jnp.int32),
              shard4((SP, QC, K.BITSET_NEGS), jnp.int32),
              shard4((SP, HP4 + 2 + K.BITSET_COLD_ROWS,
                      NSW4 * K.SW_WORD_ROWS, 128), jnp.uint32),
              shard4((SP, NSW4 * K.SW_WORD_ROWS, 128), jnp.uint32),
              _cols4(shard4), _cols4(shard4),
              shard4((SP, 2, QC, HP4 + 1), jnp.int8),
              shard4((SP, NSW4 * K.SW_ROWS, 128), jnp.float32),
              mesh=mesh4, QC=QC, nsw=NSW4, n_rows=K_ROWS)


@pytest.mark.parametrize("i", [0, 7])
def test_fused_slice_sync_four_chips(shard4, i):
    """`turbo._set_part`: one partition's columns written into the fused
    cache's own (donated) buffer; the cache stays sharded a partition
    group a chip (no gather of it onto one)."""
    from elasticsearch_tpu.parallel.turbo import _set_part

    part = jax.ShapeDtypeStruct(
        (NSW4 * K.N_CHUNKS - K.N_CHUNKS, HP4 - 31, K.CHUNK_ROWS, 128),
        jnp.int8, sharding=NamedSharding(shard4((1,), jnp.int8).sharding.mesh,
                                         P()))
    exe = _set_part.lower(_cols4(shard4), part, i=i).compile()
    assert exe.output_shardings.spec == P("shard")
    assert "all-gather" not in exe.as_text()


def test_partition_merge_four_chips(shard4, mesh4):
    from elasticsearch_tpu.parallel.spmd import _partition_merge_program

    exe = _compiled(_partition_merge_program,
                    shard4((SP, 256, 10), jnp.float32),
                    shard4((SP, 256, 10), jnp.int32), mesh=mesh4, k=10)
    assert "all-gather" in exe.as_text()


@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
def test_knn_pass1_fused_four_chips(shard4, mesh4, masked):
    from elasticsearch_tpu.parallel.knn import _pass1_fused

    QC, nw, ncp = 8, 5, 96        # 100k vectors / 12 partitions
    args = [shard4((QC, 768), jnp.float32, True),
            shard4((QC, KNN_DIMSP), jnp.int8, True),
            shard4((QC, 8), jnp.float32, True),
            shard4((SP, nw, KNN_DIMSP, K.KNN_W), jnp.int8),
            shard4((SP, nw, 4, K.KNN_W), jnp.float32),
            shard4((SP, ncp, KNN_DIMSP), jnp.float32),
            shard4((SP, ncp), jnp.float32),
            shard4((SP, ncp, nw), jnp.float32)]
    if masked:
        args.append(shard4((SP, nw, QC, K.KNN_W), jnp.int8))
    _compiled(_pass1_fused, *args, mesh=mesh4, similarity="cosine",
              C=40, nprobe=0)
