"""Pallas TPU kernels for the serving-path BM25 engine.

Why these exist (measured on the target chip): XLA's lowerings of gather /
scatter / sort on this TPU run at ~10M elements/s — scalar speed — and a
[Q, 10M] dense matmul takes tens of seconds regardless of K. The only fast
units are the MXU on well-shaped matmuls and the VPU on aligned tiles.
These kernels therefore express the classic postings-scoring hot loop
(ref: Lucene BulkScorer driven by ContextIndexSearcher.java:213-216)
entirely as matmuls and tiled vector ops:

* **Impact columns, residual int8 pairs, global scale.** Every servable
  term keeps a dense per-doc impact column quantized as TWO int8 layers
  (hi + lo residual), giving ~14-bit fixed-point precision on a STATIC
  scale (BM25 idf-free impacts are bounded by k1+1 = 2.2). Query weights
  are quantized the same way, so scoring is four exact int8 MXU matmuls
  combined in f32 — the only error is quantization + one f32 rounding,
  bounded per query by the host certificate (turbo.py).
* **Column build = scatter-as-outer-product.** Building a column from
  posting lanes needs a scatter, which TPUs lack. Within a 16384-doc tile,
  doc = hi*128 + lo; a (term, tile) group's lanes build two one-hot
  matrices A[lane, hi] and B[lane, lo]*score, and the dense [128, 128]
  tile is A^T @ B on the MXU — no scatter instruction ever executes.
* **In-kernel hierarchical windowed top-k.** Each 65536-doc superwindow
  reduces to its top NCAND (score, doc) candidates per query via a
  row-max cascade (one full pass, then NCAND cheap [512]-wide passes) —
  nothing O(n_docs) ever leaves the chip.

* **Segment-reduce for the analytics tier.** Aggregations reduce to the
  same shape: a segment's (doc, bucket-id) pairs are static, a query is a
  doc mask, and every bucket count is "sum the mask over my pairs" — a
  masked segment reduction. `agg_segment_counts` scatters each 1024-pair
  chunk into a [128, 128] bucket tile with the outer-product trick (bucket
  = hi*128 + lo within a 16384-bucket tile), batched over the query axis;
  `agg_two_level_counts` fuses the bucket level and the metric-values
  level of a sub-aggregation into ONE dispatch. Counts accumulate in f32
  one-hot matmuls — exact below 2^24 pairs, which agg_device.py gates.
  `agg_filter_counts` makes the doc mask itself, from each query's rank
  bounds, and scatters only the chunk range each row can touch: the
  chunk axis of its grid is as long as the batch's widest range, a bound
  read on the device, so one program serves every range.

* **Eager sparse impact slices for the cold tier.** Terms too sparse to
  justify a dense column (df below the cold threshold) keep their postings
  as packed ``doc << 8 | impact`` int32 lanes in a granule pool
  (pre-multiplied BM25 impacts, uint8-quantized — the BM25S eager-scoring
  representation). `sparse_gather` scatters every queried slice into a
  dense per-tile accumulator with the SAME outer-product trick as the
  column builder, then gathers the accumulated per-doc totals back at the
  slice's own lanes — so cold terms are scored on device too, and the
  host only bound-prunes + exact-rescores (turbo.py `_collect_gather`).
  One program serves a group of queries, each with an accumulator of its
  own that never leaves VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SW = 65536            # docs per superwindow (candidate granularity)
TILE = 16384          # docs per build tile (outer-product target)
SW_ROWS = SW // 128   # 512
CHUNK_ROWS = 16       # 2048 docs per score-matmul grid step
N_CHUNKS = SW_ROWS // CHUNK_ROWS   # 32 chunks per superwindow
NCAND = 17            # candidates kept per (query, superwindow)
CAND_PAD = 32         # padded candidate lane width
K1 = 1.2
COLSCALE = (K1 + 1.0) / 127.0       # hi-layer int8 step
COLSCALE2 = COLSCALE / 128.0        # lo-layer step (~14-bit combined)
MAX_GROUP_ROWS = 144  # posting rows DMA'd per build group (tile spans
#                       <= 130 rows; padded to a sublane multiple)
SPARSE_GRAN = 1024    # packed (doc, impact) lanes per slice-pool granule
SPARSE_IMP_MAX = 255  # uint8 impact quantization ceiling (doc << 8 | imp)
AGG_PAIR_GRAN = 1024  # (doc, bucket) pairs per agg segment-reduce chunk
AGG_SEG_TILE = 16384  # bucket ids per [128, 128] accumulator tile


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# query scoring kernel
# --------------------------------------------------------------------------


def _sweep_kernel(QC: int, Hpt: int):
    def kernel(qscale, hi_blk, lo_blk, wq, live_blk, out_m, out_r, acc_rm):
        c = pl.program_id(1)
        sw = pl.program_id(0)

        wh = wq[0]                                        # [QC, Hpt] i8
        wl = wq[1]
        ch = hi_blk[0]                                    # [Hpt, 16, 128] i8
        cl = lo_blk[0]
        dn = (((1,), (0,)), ((), ()))
        m_hh = jax.lax.dot_general(wh, ch, dn,
                                   preferred_element_type=jnp.int32)
        m_hl = jax.lax.dot_general(wh, cl, dn,
                                   preferred_element_type=jnp.int32)
        m_lh = jax.lax.dot_general(wl, ch, dn,
                                   preferred_element_type=jnp.int32)
        m_ll = jax.lax.dot_general(wl, cl, dn,
                                   preferred_element_type=jnp.int32)
        val = (16384.0 * m_hh.astype(jnp.float32)
               + 128.0 * (m_hl + m_lh).astype(jnp.float32)
               + m_ll.astype(jnp.float32))                # [QC, 16, 128]
        val = val * qscale[...][:, :, None]
        lv = live_blk[...]                                # [16, 128] f32
        val = jnp.where((lv[None] > 0) & (val > 0), val, -jnp.inf)
        # transposed accumulator [chunk, 16, QC]: dim 0 is untiled, so the
        # dynamic per-chunk store needs no 128-alignment proof
        acc_rm[pl.ds(c, 1), :, :] = jnp.transpose(
            jnp.max(val, axis=2))[None]

        @pl.when(c == N_CHUNKS - 1)
        def _toprows():
            # top-NCAND rows per query by (rowmax desc, row asc) — one
            # vectorized pass per candidate over the tiny [32, 16, QC]
            rm = acc_rm[...]                              # [32, 16, QC]
            rows3 = (jax.lax.broadcasted_iota(
                        jnp.int32, (N_CHUNKS, CHUNK_ROWS, QC), 0)
                     * CHUNK_ROWS
                     + jax.lax.broadcasted_iota(
                        jnp.int32, (N_CHUNKS, CHUNK_ROWS, QC), 1))
            big = jnp.int32(1 << 30)
            cand_iota = jax.lax.broadcasted_iota(
                jnp.int32, (CAND_PAD, QC), 0)
            all_m = jnp.full((CAND_PAD, QC), -jnp.inf, jnp.float32)
            all_r = jnp.zeros((CAND_PAD, QC), jnp.int32)
            for p in range(NCAND):
                m2 = jnp.max(jnp.max(rm, axis=0), axis=0,
                             keepdims=True)               # [1, QC]
                at = rm == m2[None]
                rmin = jnp.min(jnp.min(jnp.where(at, rows3, big), axis=0),
                               axis=0, keepdims=True)     # [1, QC]
                keep = (cand_iota == p) & (m2 > -jnp.inf)
                all_m = jnp.where(keep, m2, all_m)
                all_r = jnp.where(keep, rmin + sw * SW_ROWS, all_r)
                rm = jnp.where(rows3 == rmin[None], -jnp.inf, rm)
            out_m[0, :, :] = jnp.transpose(all_m)
            out_r[0, :, :] = jnp.transpose(all_r)

    return kernel


@functools.partial(jax.jit, static_argnames=("QC", "nsw"))
def sweep_rowmax(qscale, cols_hi, cols_lo, wq, live, *, QC: int, nsw: int):
    """Pass 1: sweep the column cache once for QC queries, emitting each
    128-doc posting row's max score and, per 65536-doc superwindow, the
    top-NCAND rows per query.

    qscale [QC, 1] f32 — per-query descale factor (qs2 * COLSCALE2)
    cols_hi/cols_lo [dp_chunks, Hpt, 16, 128] i8 — chunk-major columns
    wq     [2, QC, Hpt] i8 — hi/lo quantized query weights over slots
    live   [dp_rows, 128] f32

    Returns (rowmax [nsw, QC, CAND_PAD] f32, rows [nsw, QC, CAND_PAD] i32)
    with -inf padding; row ids are global (row * 128 = first doc id).
    """
    Hpt = cols_hi.shape[1]
    kernel = _sweep_kernel(QC, Hpt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nsw, N_CHUNKS),
        in_specs=[
            pl.BlockSpec((QC, 1), lambda sw, c: (0, 0),
                         memory_space=pltpu.VMEM),        # qscale
            pl.BlockSpec((1, Hpt, CHUNK_ROWS, 128),
                         lambda sw, c: (sw * N_CHUNKS + c, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Hpt, CHUNK_ROWS, 128),
                         lambda sw, c: (sw * N_CHUNKS + c, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),        # wq
            pl.BlockSpec((CHUNK_ROWS, 128),
                         lambda sw, c: (sw * N_CHUNKS + c, 0),
                         memory_space=pltpu.VMEM),        # live chunk
        ],
        out_specs=[
            pl.BlockSpec((1, QC, CAND_PAD), lambda sw, c: (sw, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, QC, CAND_PAD), lambda sw, c: (sw, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((N_CHUNKS, CHUNK_ROWS, QC), jnp.float32),  # acc_rm
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nsw, QC, CAND_PAD), jnp.float32),
            jax.ShapeDtypeStruct((nsw, QC, CAND_PAD), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )
    return fn(qscale, cols_hi, cols_lo, wq, live)


def _sweep_conj_kernel(QC: int, Hpt: int):
    def kernel(qscale, nreq, hi_blk, lo_blk, wq, wp, live_blk,
               out_m, out_r, acc_rm):
        c = pl.program_id(1)
        sw = pl.program_id(0)

        wh = wq[0]                                        # [QC, Hpt] i8
        wl = wq[1]
        ch = hi_blk[0]                                    # [Hpt, 16, 128] i8
        cl = lo_blk[0]
        dn = (((1,), (0,)), ((), ()))
        m_hh = jax.lax.dot_general(wh, ch, dn,
                                   preferred_element_type=jnp.int32)
        m_hl = jax.lax.dot_general(wh, cl, dn,
                                   preferred_element_type=jnp.int32)
        m_lh = jax.lax.dot_general(wl, ch, dn,
                                   preferred_element_type=jnp.int32)
        m_ll = jax.lax.dot_general(wl, cl, dn,
                                   preferred_element_type=jnp.int32)
        val = (16384.0 * m_hh.astype(jnp.float32)
               + 128.0 * (m_hl + m_lh).astype(jnp.float32)
               + m_ll.astype(jnp.float32))                # [QC, 16, 128]
        val = val * qscale[...][:, :, None]
        # conjunction as one extra matmul: presence = term occurs at doc
        # (the build kernel guarantees (hi, lo) != 0 exactly there), so
        # coverage == n_req iff every required clause is present and no
        # must_not clause is (must_not slots carry weight -(n_req + 1))
        present = ((ch != 0) | (cl != 0)).astype(jnp.int8)
        cov = jax.lax.dot_general(wp[...], present, dn,
                                  preferred_element_type=jnp.int32)
        lv = live_blk[...]                                # [16, 128] f32
        ok = (lv[None] > 0) & (val > 0) & (cov == nreq[...][:, :, None])
        val = jnp.where(ok, val, -jnp.inf)
        acc_rm[pl.ds(c, 1), :, :] = jnp.transpose(
            jnp.max(val, axis=2))[None]

        @pl.when(c == N_CHUNKS - 1)
        def _toprows():
            rm = acc_rm[...]                              # [32, 16, QC]
            rows3 = (jax.lax.broadcasted_iota(
                        jnp.int32, (N_CHUNKS, CHUNK_ROWS, QC), 0)
                     * CHUNK_ROWS
                     + jax.lax.broadcasted_iota(
                        jnp.int32, (N_CHUNKS, CHUNK_ROWS, QC), 1))
            big = jnp.int32(1 << 30)
            cand_iota = jax.lax.broadcasted_iota(
                jnp.int32, (CAND_PAD, QC), 0)
            all_m = jnp.full((CAND_PAD, QC), -jnp.inf, jnp.float32)
            all_r = jnp.zeros((CAND_PAD, QC), jnp.int32)
            for p in range(NCAND):
                m2 = jnp.max(jnp.max(rm, axis=0), axis=0,
                             keepdims=True)               # [1, QC]
                at = rm == m2[None]
                rmin = jnp.min(jnp.min(jnp.where(at, rows3, big), axis=0),
                               axis=0, keepdims=True)     # [1, QC]
                keep = (cand_iota == p) & (m2 > -jnp.inf)
                all_m = jnp.where(keep, m2, all_m)
                all_r = jnp.where(keep, rmin + sw * SW_ROWS, all_r)
                rm = jnp.where(rows3 == rmin[None], -jnp.inf, rm)
            out_m[0, :, :] = jnp.transpose(all_m)
            out_r[0, :, :] = jnp.transpose(all_r)

    return kernel


@functools.partial(jax.jit, static_argnames=("QC", "nsw"))
def sweep_rowmax_conj(qscale, nreq, cols_hi, cols_lo, wq, wp, live,
                      *, QC: int, nsw: int):
    """Conjunctive variant of sweep_rowmax: identical score sweep, plus a
    coverage matmul over a per-chunk presence matrix that zeroes (to -inf)
    every doc not satisfying the query's required clauses.

    nreq [QC, 1] i32 — required-clause count per query
    wp   [QC, Hpt] i8 — +1 on each required slot (must / filter / slop-0
         phrase columns), -(n_req + 1) on each must_not slot, 0 elsewhere

    A doc survives iff sum(wp[slot] * present[slot, doc]) == n_req: every
    required column nonzero there and no must_not column nonzero (one
    must_not presence drags the sum below zero, unreachable by the +1s).
    Returns the same (rowmax, rows) pair as sweep_rowmax, now bounding
    only docs that satisfy the conjunction.
    """
    Hpt = cols_hi.shape[1]
    kernel = _sweep_conj_kernel(QC, Hpt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nsw, N_CHUNKS),
        in_specs=[
            pl.BlockSpec((QC, 1), lambda sw, c: (0, 0),
                         memory_space=pltpu.VMEM),        # qscale
            pl.BlockSpec((QC, 1), lambda sw, c: (0, 0),
                         memory_space=pltpu.VMEM),        # nreq
            pl.BlockSpec((1, Hpt, CHUNK_ROWS, 128),
                         lambda sw, c: (sw * N_CHUNKS + c, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Hpt, CHUNK_ROWS, 128),
                         lambda sw, c: (sw * N_CHUNKS + c, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),        # wq
            pl.BlockSpec(memory_space=pltpu.VMEM),        # wp
            pl.BlockSpec((CHUNK_ROWS, 128),
                         lambda sw, c: (sw * N_CHUNKS + c, 0),
                         memory_space=pltpu.VMEM),        # live chunk
        ],
        out_specs=[
            pl.BlockSpec((1, QC, CAND_PAD), lambda sw, c: (sw, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, QC, CAND_PAD), lambda sw, c: (sw, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((N_CHUNKS, CHUNK_ROWS, QC), jnp.float32),  # acc_rm
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nsw, QC, CAND_PAD), jnp.float32),
            jax.ShapeDtypeStruct((nsw, QC, CAND_PAD), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )
    return fn(qscale, nreq, cols_hi, cols_lo, wq, wp, live)


ROWS_PER_STEP = 8


# --------------------------------------------------------------------------
# packed-bitset conjunction kernels
# --------------------------------------------------------------------------
#
# The coverage-matmul conjunction above multiplies a dense presence matrix
# over the FULL doc axis for every bool query — config2's 4.7x-CPU wall.
# The bitset engine replaces it with the classic packed match-set
# representation (ref: SIMD intersection of sorted integers, PAPERS.md):
# every column slot's presence packs 32 posting rows per uint32 lane word,
# clause intersection is blockwise AND / AND-NOT over those words, and the
# score sweep only runs its four MXU matmuls on 2048-doc chunks whose
# intersected mask still has a surviving bit — empty chunks cost one
# 16-lane-word test instead of four matmuls.

SW_WORD_ROWS = SW_ROWS // 32   # 16 uint32 word rows per superwindow
BITSET_CLAUSES = 8             # AND fan-in per intersect step (rarest-df
#                                clauses win; extras leave the mask a
#                                SUPERSET — the exact host rescore drops
#                                spurious survivors, so top-k is unchanged)
BITSET_NEGS = 4                # AND-NOT fan-in (largest-df prohibitions)
BITSET_COLD_ROWS = 64          # rows behind the column slots' for the match
#                                sets of COLD required / prohibited clauses
#                                (df < COLD_DF: no column to pack from), packed
#                                on the host from the postings when a bool
#                                request first names the term


@jax.jit
def pack_presence_bits(cols_hi, cols_lo):
    """Pack the column cache's presence into per-slot doc bitsets.

    cols_hi/cols_lo [dp_chunks, Hp+1, 16, 128] i8 — the serving layout.
    Presence is EXACT by the build kernel's lo >= 1 forcing, so
    (hi | lo) != 0 is the true match set of each colized term.

    Returns bits [Hp+2, dp_rows // 32, 128] u32: bit j of word
    [s, g, l] is slot s's presence at posting row 32g + j, lane l
    (doc = (32g + j) * 128 + l; one word row = two sweep chunks). Two
    sentinel slots ride along: slot Hp (the build scratch slot, always
    zero) is the AND-NOT identity and the empty mask for inactive query
    rows; appended slot Hp+1 is all-ones, the AND identity padding for
    active queries with fewer than BITSET_CLAUSES required clauses.
    """
    dpc, hp1 = cols_hi.shape[0], cols_hi.shape[1]
    p = (cols_hi != 0) | (cols_lo != 0)           # [dpc, Hp+1, 16, 128]
    p = jnp.transpose(p, (1, 0, 2, 3)).reshape(hp1, dpc // 2, 32, 128)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
    w = jnp.sum(p.astype(jnp.uint32) << shifts, axis=2)
    ones = jnp.full((1, dpc // 2, 128), 0xFFFFFFFF, jnp.uint32)
    return jnp.concatenate([w, ones], axis=0)


def _intersect_kernel():
    def kernel(q_slots, q_neg, *refs):
        pos = refs[:BITSET_CLAUSES]
        neg = refs[BITSET_CLAUSES:BITSET_CLAUSES + BITSET_NEGS]
        out = refs[BITSET_CLAUSES + BITSET_NEGS]
        acc = pos[0][0]                           # [SW_WORD_ROWS, 128] u32
        for r in pos[1:]:
            acc = acc & r[0]
        for r in neg:
            acc = acc & ~r[0]
        out[0] = acc

    return kernel


@functools.partial(jax.jit, static_argnames=("QC", "nsw"))
def intersect_bitset(q_slots, q_neg, bits, *, QC: int, nsw: int):
    """Blockwise clause intersection over the packed bitsets.

    q_slots [QC, BITSET_CLAUSES] i32 — bits slot per required clause
        (pad with a repeated clause or the all-ones sentinel; an
        inactive query row pads every clause with the all-zero sentinel
        so its mask is empty and every chunk skips)
    q_neg [QC, BITSET_NEGS] i32 — slot per must_not clause (pad with the
        all-zero sentinel, the AND-NOT identity)
    bits [Hp+2, nsw * SW_WORD_ROWS, 128] u32 — pack_presence_bits output

    The grid gathers each clause's superwindow block straight out of the
    bits array via scalar-prefetch indexed BlockSpecs (the build_columns
    idiom), so the kernel body is BITSET_CLAUSES - 1 ANDs and
    BITSET_NEGS AND-NOTs per block — pure VPU, no matmul.
    Returns mask [QC, nsw * SW_WORD_ROWS, 128] u32.
    """
    wgr = nsw * SW_WORD_ROWS
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(QC, nsw),
        in_specs=(
            [pl.BlockSpec((1, SW_WORD_ROWS, 128),
                          (lambda q, b, qs, qn, c=c: (qs[q, c], b, 0)),
                          memory_space=pltpu.VMEM)
             for c in range(BITSET_CLAUSES)]
            + [pl.BlockSpec((1, SW_WORD_ROWS, 128),
                            (lambda q, b, qs, qn, n=n: (qn[q, n], b, 0)),
                            memory_space=pltpu.VMEM)
               for n in range(BITSET_NEGS)]),
        out_specs=pl.BlockSpec((1, SW_WORD_ROWS, 128),
                               lambda q, b, qs, qn: (q, b, 0),
                               memory_space=pltpu.VMEM),
    )
    fn = pl.pallas_call(
        _intersect_kernel(),
        name="intersect_bitset",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((QC, wgr, 128), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )
    return fn(q_slots, q_neg,
              *([bits] * (BITSET_CLAUSES + BITSET_NEGS)))


@functools.partial(jax.jit, donate_argnums=(0,))
def bitset_repack(bits, cols_hi, cols_lo):
    """`pack_presence_bits` of the column cache written over the first
    Hp + 2 rows of the (donated) bitset array; the cold rows behind them
    stay as they are."""
    return jax.lax.dynamic_update_slice(
        bits, pack_presence_bits(cols_hi, cols_lo), (0, 0, 0))


@functools.partial(jax.jit, donate_argnums=(0,))
def bitset_write_rows(bits, idx, rows):
    """Write whole rows (host-packed match sets of cold clauses) into the
    (donated) bitset array: bits [R, wgr, 128] u32, idx [n] i32, rows
    [n, wgr, 128] u32. A padding entry repeats a real one."""
    return bits.at[idx].set(rows)


@jax.jit
def mask_live_counts(mask, live_bits):
    """Per-query population count of the intersected match set among live
    docs: the conjunction's exact hit count on this partition. mask
    [QC, wgr, 128] u32, live_bits [wgr, 128] u32 (the live mask packed as
    the bitset rows are). Returns [QC] i32."""
    return jnp.sum(jax.lax.population_count(mask & live_bits[None]),
                   axis=(1, 2)).astype(jnp.int32)


@jax.jit
def mask_chunk_counts(mask):
    """Per-query count of 2048-doc chunks with any surviving bit —
    the skipped-block telemetry source (total chunks minus this).

    mask [QC, wgr, 128] u32; each word row g holds chunks 2g (low 16
    bits) and 2g + 1 (high 16). Returns [QC] i32.
    """
    lo = jnp.any((mask & jnp.uint32(0xFFFF)) != 0, axis=-1)
    hi = jnp.any((mask >> jnp.uint32(16)) != 0, axis=-1)
    return (jnp.sum(lo, axis=-1) + jnp.sum(hi, axis=-1)).astype(jnp.int32)


def _sweep_bitset_kernel(QC: int, Hpt: int):
    def kernel(qscale, hi_blk, lo_blk, wq, mask_blk, live_blk,
               out_m, out_r, acc_rm):
        c = pl.program_id(1)
        sw = pl.program_id(0)

        # expand this chunk's 16-bit half of the intersected word row
        w = mask_blk[:, pl.ds(c // 2, 1), :][:, 0, :]     # [QC, 128] u32
        shifts = (jax.lax.broadcasted_iota(
            jnp.int32, (1, CHUNK_ROWS, 1), 1)
            + (c % 2) * CHUNK_ROWS).astype(jnp.uint32)
        alive = (jnp.right_shift(w[:, None, :], shifts)
                 & jnp.uint32(1)) != 0                    # [QC, 16, 128]
        nz = jnp.any(alive)

        @pl.when(nz)
        def _score():
            wh = wq[0]                                    # [QC, Hpt] i8
            wl = wq[1]
            ch = hi_blk[0]                                # [Hpt, 16, 128]
            cl = lo_blk[0]
            dn = (((1,), (0,)), ((), ()))
            m_hh = jax.lax.dot_general(wh, ch, dn,
                                       preferred_element_type=jnp.int32)
            m_hl = jax.lax.dot_general(wh, cl, dn,
                                       preferred_element_type=jnp.int32)
            m_lh = jax.lax.dot_general(wl, ch, dn,
                                       preferred_element_type=jnp.int32)
            m_ll = jax.lax.dot_general(wl, cl, dn,
                                       preferred_element_type=jnp.int32)
            val = (16384.0 * m_hh.astype(jnp.float32)
                   + 128.0 * (m_hl + m_lh).astype(jnp.float32)
                   + m_ll.astype(jnp.float32))            # [QC, 16, 128]
            val = val * qscale[...][:, :, None]
            lv = live_blk[...]                            # [16, 128] f32
            val = jnp.where((lv[None] > 0) & (val > 0) & alive,
                            val, -jnp.inf)
            acc_rm[pl.ds(c, 1), :, :] = jnp.transpose(
                jnp.max(val, axis=2))[None]

        @pl.when(jnp.logical_not(nz))
        def _skip():
            # the scratch row is reused across superwindows — a skipped
            # chunk must still overwrite last round's values
            acc_rm[pl.ds(c, 1), :, :] = jnp.full(
                (1, CHUNK_ROWS, QC), -jnp.inf, jnp.float32)

        @pl.when(c == N_CHUNKS - 1)
        def _toprows():
            rm = acc_rm[...]                              # [32, 16, QC]
            rows3 = (jax.lax.broadcasted_iota(
                        jnp.int32, (N_CHUNKS, CHUNK_ROWS, QC), 0)
                     * CHUNK_ROWS
                     + jax.lax.broadcasted_iota(
                        jnp.int32, (N_CHUNKS, CHUNK_ROWS, QC), 1))
            big = jnp.int32(1 << 30)
            cand_iota = jax.lax.broadcasted_iota(
                jnp.int32, (CAND_PAD, QC), 0)
            all_m = jnp.full((CAND_PAD, QC), -jnp.inf, jnp.float32)
            all_r = jnp.zeros((CAND_PAD, QC), jnp.int32)
            for p in range(NCAND):
                m2 = jnp.max(jnp.max(rm, axis=0), axis=0,
                             keepdims=True)               # [1, QC]
                at = rm == m2[None]
                rmin = jnp.min(jnp.min(jnp.where(at, rows3, big), axis=0),
                               axis=0, keepdims=True)     # [1, QC]
                keep = (cand_iota == p) & (m2 > -jnp.inf)
                all_m = jnp.where(keep, m2, all_m)
                all_r = jnp.where(keep, rmin + sw * SW_ROWS, all_r)
                rm = jnp.where(rows3 == rmin[None], -jnp.inf, rm)
            out_m[0, :, :] = jnp.transpose(all_m)
            out_r[0, :, :] = jnp.transpose(all_r)

    return kernel


@functools.partial(jax.jit, static_argnames=("QC", "nsw"))
def sweep_rowmax_bitset(qscale, cols_hi, cols_lo, wq, mask, live,
                        *, QC: int, nsw: int):
    """Bitset variant of sweep_rowmax_conj: the intersected match-set
    mask (intersect_bitset output) replaces the per-chunk coverage
    matmul, and chunks whose mask half-word is all-zero skip the four
    score matmuls entirely — a selective lead term turns the full-cache
    sweep into a sparse one.

    mask [QC, nsw * SW_WORD_ROWS, 128] u32 — chunk c of superwindow sw
    reads word row sw * SW_WORD_ROWS + c // 2, bit half c % 2.
    Returns the same (rowmax, rows) pair as sweep_rowmax_conj; the mask
    is a superset of the true match set when a query carries more than
    BITSET_CLAUSES / BITSET_NEGS clauses, so the caller's exact rescore
    (which re-tests every clause) remains the source of truth.
    """
    Hpt = cols_hi.shape[1]
    kernel = _sweep_bitset_kernel(QC, Hpt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nsw, N_CHUNKS),
        in_specs=[
            pl.BlockSpec((QC, 1), lambda sw, c: (0, 0),
                         memory_space=pltpu.VMEM),        # qscale
            pl.BlockSpec((1, Hpt, CHUNK_ROWS, 128),
                         lambda sw, c: (sw * N_CHUNKS + c, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Hpt, CHUNK_ROWS, 128),
                         lambda sw, c: (sw * N_CHUNKS + c, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),        # wq
            # the superwindow's 16 word rows (fetched once per sw; the
            # TPU lowering refuses a 1-sublane block of a middle axis) —
            # the kernel picks its chunk's row
            pl.BlockSpec((QC, SW_WORD_ROWS, 128), lambda sw, c: (0, sw, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((CHUNK_ROWS, 128),
                         lambda sw, c: (sw * N_CHUNKS + c, 0),
                         memory_space=pltpu.VMEM),        # live chunk
        ],
        out_specs=[
            pl.BlockSpec((1, QC, CAND_PAD), lambda sw, c: (sw, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, QC, CAND_PAD), lambda sw, c: (sw, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((N_CHUNKS, CHUNK_ROWS, QC), jnp.float32),  # acc_rm
        ],
    )
    fn = pl.pallas_call(
        kernel,
        name="sweep_rowmax_bitset",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nsw, QC, CAND_PAD), jnp.float32),
            jax.ShapeDtypeStruct((nsw, QC, CAND_PAD), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )
    return fn(qscale, cols_hi, cols_lo, wq, mask, live)


# --------------------------------------------------------------------------
# partition-merge kernel
# --------------------------------------------------------------------------


def _merge_kernel(k: int, KP: int):
    def kernel(s_ref, o_ref, out_s, out_p, out_o):
        s = s_ref[...]                                    # [QB, L] f32
        o = o_ref[...]                                    # [QB, L] i32
        QB = s.shape[0]
        # lane layout is partition-major: lane = partition * k + slot
        p = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // k
        s = jnp.where(s > 0, s, 0.0)
        kiota = jax.lax.broadcasted_iota(jnp.int32, (QB, KP), 1)
        acc_s = jnp.zeros((QB, KP), jnp.float32)
        acc_p = jnp.zeros((QB, KP), jnp.int32)
        acc_o = jnp.zeros((QB, KP), jnp.int32)
        big = jnp.int32(1 << 30)
        for j in range(k):
            m = jnp.max(s, axis=1, keepdims=True)         # [QB, 1]
            at = (s == m) & (m > 0)
            pmin = jnp.min(jnp.where(at, p, big), axis=1, keepdims=True)
            at2 = at & (p == pmin)
            omin = jnp.min(jnp.where(at2, o, big), axis=1, keepdims=True)
            sel = at2 & (o == omin)
            keep = (kiota == j) & (m > 0)
            acc_s = jnp.where(keep, m, acc_s)
            acc_p = jnp.where(keep, pmin, acc_p)
            acc_o = jnp.where(keep, omin, acc_o)
            s = jnp.where(sel, 0.0, s)
        out_s[...] = acc_s
        out_p[...] = acc_p
        out_o[...] = acc_o

    return kernel


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(scores, ords, *, k: int):
    """Dense deterministic merge of per-partition top-k candidate lanes.

    scores [Q, S*k] f32 — lane = partition * k + slot; non-positive lanes
        are empty and never selected
    ords   [Q, S*k] i32 — per-partition doc ordinals aligned with scores

    Selection is a k-step max cascade (the _toprows idiom: XLA sort runs
    at scalar speed on this TPU, k passes of tiled VPU reductions do not)
    with the (score desc, partition asc, ord asc) tie-break resolved by
    two nested min-reductions per step — exactly the host _merge3
    lexicographic order. Empty output slots are (0, 0, 0).
    Returns (scores [Q, k] f32, parts [Q, k] i32, ords [Q, k] i32).
    """
    Q, L = scores.shape
    QB = -(-max(Q, 1) // 8) * 8
    LP = -(-max(L, 1) // 128) * 128
    KP = -(-k // 128) * 128
    s = jnp.pad(scores, ((0, QB - Q), (0, LP - L)))
    o = jnp.pad(ords.astype(jnp.int32), ((0, QB - Q), (0, LP - L)))
    kernel = _merge_kernel(k, KP)
    fn = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((QB, KP), jnp.float32),
            jax.ShapeDtypeStruct((QB, KP), jnp.int32),
            jax.ShapeDtypeStruct((QB, KP), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )
    out_s, out_p, out_o = fn(s, o)
    return out_s[:Q, :k], out_p[:Q, :k], out_o[:Q, :k]


# --------------------------------------------------------------------------
# column builder kernel
# --------------------------------------------------------------------------


def _build_kernel():
    def kernel(g_rows, g_nrows, g_base, g_slot,
               lane_docs, lane_scores, hi_in, lo_in, out_hi, out_lo,
               dbuf, vbuf, sem):
        g = pl.program_id(0)
        r0 = g_rows[g]
        cp = pltpu.make_async_copy(
            lane_docs.at[pl.ds(r0, MAX_GROUP_ROWS)], dbuf, sem)
        cp.start()
        cp.wait()
        cp2 = pltpu.make_async_copy(
            lane_scores.at[pl.ds(r0, MAX_GROUP_ROWS)], vbuf, sem)
        cp2.start()
        cp2.wait()
        nrows = g_nrows[g]
        base = g_base[g]
        col = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)

        def row_body(r, tacc):
            d = dbuf[pl.ds(r, 1), :][0]
            v = vbuf[pl.ds(r, 1), :][0]
            ok = (d >= base) & (d < base + TILE)
            rel = jnp.where(ok, d - base, 0)
            veff = jnp.where(ok, v, 0.0)
            hi = jax.lax.shift_right_logical(rel, 7)[:, None]
            lo = jnp.bitwise_and(rel, 127)[:, None]
            A = jnp.where(col == hi, 1.0, 0.0)
            Bm = jnp.where(col == lo, veff[:, None], 0.0)
            return tacc + jax.lax.dot_general(
                A, Bm, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        tacc = jax.lax.fori_loop(
            0, nrows, row_body, jnp.zeros((128, 128), jnp.float32))
        hi_t = jnp.clip(jnp.round(tacc * (1.0 / COLSCALE)), -127, 127)
        lo_t = jnp.clip(jnp.round(
            (tacc - hi_t * COLSCALE) * (1.0 / COLSCALE2)), -127, 127)
        # presence exactness: a cell with a real posting (tacc > 0) must
        # stay nonzero in (hi, lo) so the conjunctive sweep's presence mask
        # sees it; the per-term certificate error widens from half a lo
        # step to a full one to cover the forced value (turbo.py e_q)
        lo_t = jnp.where((tacc > 0) & (hi_t == 0) & (lo_t == 0), 1.0, lo_t)
        hi8 = hi_t.astype(jnp.int8)
        lo8 = lo_t.astype(jnp.int8)
        for u in range(TILE // 2048):                     # 8 chunk-majors
            out_hi[u, 0, :, :] = hi8[u * 16:(u + 1) * 16, :]
            out_lo[u, 0, :, :] = lo8[u * 16:(u + 1) * 16, :]

    return kernel


@functools.partial(jax.jit, static_argnames=("n_groups",),
                   donate_argnums=(6, 7))
def build_columns(g_rows, g_nrows, g_base, g_slot,
                  lane_docs, lane_scores, cols_hi, cols_lo,
                  *, n_groups: int):
    """Fill int8 hi/lo column tiles on device from posting lanes.

    One grid step = one (column slot, 16384-doc tile) group. Groups
    partition each term's lanes by tile, so every step owns a distinct
    output tile — no read-modify-write. A tile overlaps at most 130
    posting rows (128 interior + 2 straddlers), so MAX_GROUP_ROWS rows
    always suffice; rows straddling a tile boundary appear in both
    neighbors' groups with complementary masks.

    g_rows [NG] i32 — first posting row of each group
    g_nrows [NG] i32 — rows to process (0 writes a zero tile — used both
        for padding groups, pointed at the scratch slot, and to clear an
        evicted term's tiles)
    g_base [NG] i32 — absolute first doc of the group's tile
    g_slot [NG] i32 — destination slot
    lane_docs/lane_scores [tr, 128] — block-posting lane arrays with
        >= MAX_GROUP_ROWS trailing padding rows
    cols_hi/cols_lo [dp_chunks, Hpt, 16, 128] i8 (donated) — the column
    cache layers in the chunk-major serving layout; a build tile spans 8
    consecutive chunk-majors of its slot.
    """
    kernel = _build_kernel()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_groups,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),   # cols_hi (aliased)
            pl.BlockSpec(memory_space=pl.ANY),   # cols_lo (aliased)
        ],
        out_specs=[
            pl.BlockSpec(
                (TILE // 2048, 1, CHUNK_ROWS, 128),
                lambda g, gr, gn, gb, gs: (gb[g] // TILE, gs[g], 0, 0),
                memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (TILE // 2048, 1, CHUNK_ROWS, 128),
                lambda g, gr, gn, gb, gs: (gb[g] // TILE, gs[g], 0, 0),
                memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((MAX_GROUP_ROWS, 128), jnp.int32),
            pltpu.VMEM((MAX_GROUP_ROWS, 128), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(cols_hi.shape, jnp.int8),
            jax.ShapeDtypeStruct(cols_lo.shape, jnp.int8),
        ],
        input_output_aliases={6: 0, 7: 1},
        interpret=_interpret(),
    )
    return fn(g_rows, g_nrows, g_base, g_slot, lane_docs, lane_scores,
              cols_hi, cols_lo)


# --------------------------------------------------------------------------
# eager sparse impact gather kernel (cold tier on device)
# --------------------------------------------------------------------------


SG_SCATTER = 1 << 16   # a step's kind and flag, OR-ed over its tile id in
SG_PICK = 1 << 17      # the step's `meta` word (neither kind = a padding
SG_FIRST = 1 << 18     # step); FIRST = a query's first scatter step, a
#                        chunk's first pick step


def _sparse_gather_kernel(n_tiles: int):
    def kernel(coff, cw, meta, oidx, pool_blk, out_ref, acc_ref):
        i = pl.program_id(0)
        m = meta[i]
        t = jnp.bitwise_and(m, SG_SCATTER - 1)
        first = jnp.bitwise_and(m, SG_FIRST) != 0
        base = t * TILE
        col = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)

        def lanes(r):
            v = pool_blk[0, r, :]                         # [128] i32 packed
            doc = jax.lax.shift_right_logical(v, 8)
            imp = jnp.bitwise_and(v, SPARSE_IMP_MAX)
            rel = doc - base
            ok = (imp > 0) & (rel >= 0) & (rel < TILE)
            rel = jnp.where(ok, rel, 0)
            hi = jax.lax.shift_right_logical(rel, 7)[:, None]
            lo = jnp.bitwise_and(rel, 127)[:, None]
            return ok, imp, jnp.where(col == hi, 1.0, 0.0), lo

        @pl.when(jnp.bitwise_and(m, SG_SCATTER) != 0)
        def _scatter():
            @pl.when(first)
            def _new_query():
                def zero(tt, carry):
                    acc_ref[tt] = jnp.zeros((128, 128), jnp.float32)
                    return carry
                jax.lax.fori_loop(0, n_tiles, zero, 0)

            w = cw[i]
            tacc = jnp.zeros((128, 128), jnp.float32)
            for r in range(SPARSE_GRAN // 128):
                ok, imp, A, lo = lanes(r)
                val = jnp.where(ok, imp.astype(jnp.float32) * w, 0.0)
                Bm = jnp.where(col == lo, val[:, None], 0.0)
                tacc = tacc + jax.lax.dot_general(
                    A, Bm, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            acc_ref[t] += tacc

        @pl.when(jnp.bitwise_and(m, SG_PICK) != 0)
        def _pick():
            @pl.when(first)
            def _new_chunk():
                out_ref[...] = jnp.zeros((1, SPARSE_GRAN // 128, 128),
                                         jnp.float32)

            acc = acc_ref[t]                              # [128, 128] f32
            rows = []
            for r in range(SPARSE_GRAN // 128):
                ok, _, A, lo = lanes(r)
                # gather-as-matmul: G[j] = acc[hi_j, :], then mask the lo
                # lane — the transpose of the scatter trick, MXU + VPU only
                G = jax.lax.dot_general(
                    A, acc, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [128, 128]
                g = jnp.sum(jnp.where(col == lo, G, 0.0), axis=1)
                rows.append(jnp.where(ok, g, 0.0)[None])
            out_ref[0, :, :] += jnp.concatenate(rows, axis=0)

    return kernel


@functools.partial(jax.jit, static_argnames=("n_chunks", "n_tiles"))
def sparse_gather(desc, pool, *, n_chunks: int, n_tiles: int):
    """Cold-term eager sparse scoring for a GROUP of queries in one
    program: a flat list of steps, each one (1024-lane slice chunk,
    16384-doc tile) pair that the host found to meet, walked query by
    query. A query's SCATTER steps build its dense per-doc accumulator
    [n_tiles, 128, 128] f32, which lives in VMEM and nowhere else
    (scatter-as-outer-product, exactly the build_columns idiom: within a
    tile doc = hi*128 + lo, so A[lane, hi] and B[lane, lo]*impact make the
    tile A^T @ B on the MXU); its PICK steps, the same pairs again, read
    the accumulated totals back at each chunk's own lanes. Slices of
    different terms of ONE query scatter into the same cells, so the value
    read back at any lane is the doc's FULL cold contribution for that
    query — the host needs no posting-list walk, only the bound-prune +
    exact top-k rescore (turbo.py `_collect_gather`); the accumulator is
    zeroed at a query's first step, so slices of different queries never
    meet. Within a tile a query's chunks add in the order of its steps.

    desc [4, n_steps] i32, one upload — a row per step field:
      coff — the step's pool granule (granule 0 is the reserved all-zero
        one; consecutive steps of one chunk fetch it once)
      cw   — f32 bits of the chunk's dequant weight (idf * boost * slice
        quantization scale)
      meta — the tile id | SG_SCATTER or SG_PICK | SG_FIRST; 0 = padding
      oidx — the output chunk a pick step adds into (steps sorted by it,
        so a chunk is written once); a scatter or padding step repeats
        its predecessor's so that nothing is written back in between
    pool [G, 8, 128] i32 — packed slice granules, ``doc << 8 | impact``
        (uint8 impact, so doc ids must fit 23 bits — turbo.py gates)

    Returns [n_chunks, 8, 128] f32 — accumulated cold totals, lane-aligned
    with the pool granules of the group's chunks; chunks no pick step
    names are left unwritten. Shape set: (n_steps, n_chunks, pool
    granules, n_tiles).
    """
    return pl.pallas_call(
        _sparse_gather_kernel(n_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(desc.shape[1],),
            in_specs=[
                pl.BlockSpec(
                    (1, SPARSE_GRAN // 128, 128),
                    lambda i, coff, cw, meta, oidx: (coff[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, SPARSE_GRAN // 128, 128),
                lambda i, coff, cw, meta, oidx: (oidx[i], 0, 0)),
            scratch_shapes=[pltpu.VMEM((n_tiles, 128, 128), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (n_chunks, SPARSE_GRAN // 128, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )(desc[0], jax.lax.bitcast_convert_type(desc[1], jnp.float32),
      desc[2], desc[3], pool)


@functools.partial(jax.jit, donate_argnums=(0,))
def sparse_pool_update(pool, idx, upd):
    """Write freshly built slice granules into the (donated) device pool
    in place — the slice twin of the build kernel's aliased column
    update. Padding rows point at granule 0 with all-zero payloads, so
    the reserved zero granule stays zero."""
    return pool.at[idx].set(upd)


# --------------------------------------------------------------------------
# analytics-tier segment reduce (agg_device.py)
# --------------------------------------------------------------------------


def _agg_chunk(q, i, c0, c1):
    """The pair chunk step `i` of row `q` works on: the `i`-th of the row's
    range [c0[q], c1[q]). A step past the range repeats the range's last
    chunk (chunk 0 for an empty range), so its blocks are not fetched
    again, and the kernel's gate leaves it out."""
    return jnp.clip(c0[q] + i, 0, jnp.maximum(c1[q], 1) - 1)


def _agg_count_kernel():
    def kernel(ct0, ct1, c0, c1, sel_blk, seg_blk, acc_ref):
        q = pl.program_id(0)
        t = pl.program_id(1)
        i = pl.program_id(2)
        c = _agg_chunk(q, i, c0, c1)

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros((1, 1, 128, 128), jnp.float32)

        # pairs are grouped, so the host-prefetched inclusive bucket-tile
        # range [ct0, ct1] skips every tile a chunk cannot touch (padding
        # chunks carry the empty range (1, 0) and never scatter); a step
        # past the row's chunk range [c0, c1) scatters nothing (the axis
        # is as long as the batch's widest row; a padding row of the
        # batch carries the empty range)
        @pl.when((c0[q] + i < c1[q]) & (t >= ct0[c]) & (t <= ct1[c]))
        def _scatter():
            base = t * AGG_SEG_TILE
            col = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
            tacc = jnp.zeros((128, 128), jnp.float32)
            for r in range(AGG_PAIR_GRAN // 128):
                seg = seg_blk[0, r, :]                    # [128] i32 bucket
                val = sel_blk[0, 0, r, :]                 # [128] f32 0/1
                rel = seg - base
                ok = (seg >= 0) & (rel >= 0) & (rel < AGG_SEG_TILE)
                rel = jnp.where(ok, rel, 0)
                v = jnp.where(ok, val, 0.0)
                hi = jax.lax.shift_right_logical(rel, 7)[:, None]
                lo = jnp.bitwise_and(rel, 127)[:, None]
                A = jnp.where(col == hi, 1.0, 0.0)
                Bm = jnp.where(col == lo, v[:, None], 0.0)
                tacc = tacc + jax.lax.dot_general(
                    A, Bm, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            acc_ref[0, 0, :, :] += tacc

    return kernel


def _agg_scatter(sel, seg, ct0, ct1, n_tiles: int, name: str, crange=None):
    """The segment reduction proper: acc[q, s] = sum of sel[q, i] over the
    pairs i with seg[i] == s — the scatter-as-outer-product trick applied
    to bucket ids (within a 16384-bucket tile, bucket = hi*128 + lo).
    `sel` [Q, p] f32 0/1 is the selection already laid out in pair order,
    so the kernel is scatter-only. `name` names the custom call: a trace
    reader finds the kernel by the instruction's own name. `crange`
    [Q, 2] i32: row q's selection is all zero outside the pair chunks
    [crange[q, 0], crange[q, 1]), so the grid's chunk axis is only as
    long as the widest row's range (a grid bound read on the device: one
    program whatever the ranges) and each row starts at its own first
    chunk; (0, 0) = a row with nothing to count. None = every row, every
    chunk. Returns [Q, n_tiles * AGG_SEG_TILE] f32 (exact integers below
    2^24)."""
    Q, p = sel.shape
    nc = p // AGG_PAIR_GRAN
    if crange is None:
        c0, c1, span = jnp.zeros((Q,), jnp.int32), \
            jnp.full((Q,), nc, jnp.int32), nc
    else:
        c0, c1 = crange[:, 0], crange[:, 1]
        span = jnp.maximum(jnp.max(c1 - c0), 1)
    acc = pl.pallas_call(
        _agg_count_kernel(),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(Q, n_tiles, span),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, AGG_PAIR_GRAN // 128, 128),
                    lambda q, t, i, ct0, ct1, c0, c1: (
                        q, _agg_chunk(q, i, c0, c1), 0, 0)),
                pl.BlockSpec(
                    (1, AGG_PAIR_GRAN // 128, 128),
                    lambda q, t, i, ct0, ct1, c0, c1: (
                        _agg_chunk(q, i, c0, c1), 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, 128, 128),
                lambda q, t, i, ct0, ct1, c0, c1: (q, t, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((Q, n_tiles, 128, 128),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
        name=name,
    )(ct0, ct1, c0, c1,
      sel.reshape(Q, nc, AGG_PAIR_GRAN // 128, 128),
      seg.reshape(nc, AGG_PAIR_GRAN // 128, 128))
    return acc.reshape(Q, n_tiles * AGG_SEG_TILE)


def _agg_counts(mask, doc, seg, ct0, ct1, n_segments: int, name: str):
    """One masked segment reduction: counts[q, s] = |{pairs (d, s) with
    mask[q, d]}|. Pre-gathering the mask at the pair docs keeps the
    kernel scatter-only, the same split as `_segment_count_program` used
    before this kernel existed."""
    n_tiles = -(-n_segments // AGG_SEG_TILE)
    sel = jnp.take(mask, doc, axis=1).astype(jnp.float32)
    flat = _agg_scatter(sel, seg, ct0, ct1, n_tiles, name)
    return flat[:, :n_segments].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("p", "n_segments"))
def agg_segment_counts(mask, blob, *, p: int, n_segments: int):
    """Batched bucket counting for one agg layout: one device dispatch
    answers Q queries' doc counts over the layout's static (doc, bucket)
    pairs. `blob` is the layout's single device-resident i32 column —
    sections [doc pairs | bucket pairs | ct0 | ct1] — so the HBM ledger
    and the scrub registry see exactly one region per layout.

    mask [Q, n_docs] bool — one query mask per batched agg work
    blob [2p + 2(p/1024)] i32 — p 1024-aligned; pad pairs carry doc 0 /
        bucket -1 (the kernel's ok-gate drops them)

    Returns [Q, n_segments] i32 — exact doc counts per bucket (f32
    accumulation, exact while p < 2^24 — agg_device.py gates)."""
    nc = p // AGG_PAIR_GRAN
    return _agg_counts(mask, blob[:p], blob[p:2 * p],
                       blob[2 * p:2 * p + nc],
                       blob[2 * p + nc:2 * p + 2 * nc], n_segments,
                       "agg_segment_counts")


@functools.partial(jax.jit, static_argnames=("p", "n_out", "identity"))
def agg_filter_counts(bounds, crange, cols, blob, *, p: int, n_out: int,
                      identity: bool):
    """`agg_segment_counts` for a match set that is MADE HERE, from each
    query's bounds, and never crosses from the host: one (segment,
    layout) reduction of the filter + bucket route (agg_device.py).

    bounds [Q, F, 2] i32 — query q keeps a doc when, for every filter
        column f, bounds[q, f, 0] <= cols[f][doc] < bounds[q, f, 1]. A
        column holds each doc's RANK among the segment's sorted distinct
        values of the field (-1 = no value), so the host's binary search
        of a request's bounds makes the comparison exact to the last
        bit of the field. (-1, INT32_MAX) leaves a column unconstrained;
        (0, 0) keeps nothing (a padding row).
    crange [Q, 2] i32 — the layout's 1024-pair chunks [crange[q, 0],
        crange[q, 1]) hold every pair whose doc query q keeps (the host
        knows from its zone maps of the columns, agg_device.py); the
        scatter runs those chunks and no others. (0, nc) = all of them;
        (0, 0) = none (a padding row, a range that misses the segment)
    cols  — tuple of F [n_docs] i32 rank columns of the segment
    blob  — the layout's column, as `agg_segment_counts` takes it
    identity — the layout's pair i IS doc i (every doc holds exactly one
        value of the bucketed field): the selection is laid out in pair
        order by padding, with no gather

    Returns ([Q, n_out] i32 counts per bucket rank, n_out >= the
    layout's ranks; [Q] i32 size of each query's match set)."""
    nc = p // AGG_PAIR_GRAN
    Q = bounds.shape[0]
    n = cols[0].shape[0]
    keep = jnp.ones((Q, n), jnp.bool_)
    for f, col in enumerate(cols):
        keep = keep & (col[None, :] >= bounds[:, f, 0:1]) \
            & (col[None, :] < bounds[:, f, 1:2])
    totals = jnp.sum(keep, axis=1, dtype=jnp.int32)
    if identity:
        sel = jnp.pad(keep, ((0, 0), (0, p - n)))
    else:
        sel = jnp.take(keep, blob[:p], axis=1)
    n_tiles = -(-n_out // AGG_SEG_TILE)
    flat = _agg_scatter(sel.astype(jnp.float32), blob[p:2 * p],
                        blob[2 * p:2 * p + nc],
                        blob[2 * p + nc:2 * p + 2 * nc], n_tiles,
                        "agg_filter_counts", crange)
    return flat[:, :n_out].astype(jnp.int32), totals


@functools.partial(jax.jit, static_argnames=("pd", "pm", "n_segments"))
def agg_two_level_counts(mask, blob, *, pd: int, pm: int, n_segments: int):
    """Fused two-level reduction for metric-under-bucket sub-aggs: ONE
    dispatch returns both the bucket doc counts (level 1, over the
    (doc, bucket) pairs) and the bucket value counts (level 2, over the
    bucket × metric-value cross pairs) — instead of B per-bucket sweeps.
    Host-side exact refinement then splits the pre-sorted metric values
    at the value-count boundaries (agg_device.py), so float metrics keep
    the host aggregators' exact summation order.

    blob sections: [doc(pd) | seg(pd) | dct0 | dct1 | mdoc(pm) |
    mseg(pm) | mct0 | mct1], all i32, pair sections 1024-aligned.

    Returns ([Q, n_segments] i32 doc counts, [Q, n_segments] i32 value
    counts)."""
    ncd = pd // AGG_PAIR_GRAN
    ncm = pm // AGG_PAIR_GRAN
    o = 2 * pd + 2 * ncd
    dc = _agg_counts(mask, blob[:pd], blob[pd:2 * pd],
                     blob[2 * pd:2 * pd + ncd],
                     blob[2 * pd + ncd:o], n_segments,
                     "agg_two_level_counts")
    vc = _agg_counts(mask, blob[o:o + pm], blob[o + pm:o + 2 * pm],
                     blob[o + 2 * pm:o + 2 * pm + ncm],
                     blob[o + 2 * pm + ncm:o + 2 * pm + 2 * ncm],
                     n_segments, "agg_two_level_counts")
    return dc, vc


# --------------------------------------------------------------------------
# quantized kNN first-pass kernel (PR 19)
# --------------------------------------------------------------------------

KNN_W = 2048          # docs per kNN window (candidate granularity)
KNN_CANDW = 32        # candidates kept per (query, window)


def _knn_pass_kernel(similarity: str, masked: bool):
    def kernel(qi8, qmeta, q8_blk, meta_blk, act_blk, *rest):
        if masked:
            fmask_blk, out_s, out_r = rest
        else:
            out_s, out_r = rest
        w = pl.program_id(0)
        dn = (((1,), (0,)), ((), ()))
        dot = jax.lax.dot_general(
            qi8[...], q8_blk[0], dn,
            preferred_element_type=jnp.int32)              # [QC, KNN_W]
        meta = meta_blk[0]                                 # [4, KNN_W]
        scale = meta[0:1, :]                               # per-row int8 step
        row_l1 = meta[1:2, :]                              # dequantized L1
        nrm = meta[2:3, :]                                 # stored-row L2
        okf = meta[3:4, :]                                 # exists & live
        qm = qmeta[...]                                    # [QC, 8]
        sq = qm[:, 0:1]
        est = dot.astype(jnp.float32) * (scale * sq)
        # certified optimism: |true_dot - est| <= halfsq*row_l1
        # + (0.5*ql1 + dims*sq/4)*scale (quantization) plus 2^-7*|q||v|
        # covering the reference's bf16 cast + f32 accumulation; the 1.05
        # inflation covers f32 rounding of the slack arithmetic itself
        slack = (qm[:, 5:6] * row_l1 + qm[:, 1:2] * scale
                 + 0.0079 * qm[:, 2:3] * nrm)
        dot_best = est + slack * 1.05 + 1e-6
        if similarity == "cosine":
            opt = (1.0 + dot_best * qm[:, 4:5]) * 0.5
        elif similarity == "dot_product":
            opt = (1.0 + dot_best) * 0.5
        else:   # l2_norm: larger dot -> smaller distance -> larger score
            d2 = jnp.maximum(qm[:, 3:4] + nrm * nrm - 2.0 * dot_best, 0.0)
            opt = 1.0 / (1.0 + jnp.sqrt(d2))
        ok = (okf > 0) & (act_blk[0] > 0)                  # act [QC, 1]
        if masked:
            # widened first: the v5e VPU has no int8 compare
            ok = ok & (fmask_blk[0].astype(jnp.int32) > 0)
        opt = jnp.where(ok, opt, -jnp.inf)
        QC = opt.shape[0]
        cols = jax.lax.broadcasted_iota(jnp.int32, (QC, KNN_W), 1)
        cand_iota = jax.lax.broadcasted_iota(
            jnp.int32, (QC, KNN_CANDW), 1)
        big = jnp.int32(1 << 30)
        acc_s = jnp.full((QC, KNN_CANDW), -jnp.inf, jnp.float32)
        acc_r = jnp.zeros((QC, KNN_CANDW), jnp.int32)
        # KNN_CANDW-pass max cascade (the _toprows idiom — XLA sort runs
        # at scalar speed on this TPU), tie-break (opt desc, row asc)
        for p in range(KNN_CANDW):
            m = jnp.max(opt, axis=1, keepdims=True)        # [QC, 1]
            at = opt == m
            rmin = jnp.min(jnp.where(at, cols, big), axis=1, keepdims=True)
            keep = (cand_iota == p) & (m > -jnp.inf)
            acc_s = jnp.where(keep, m, acc_s)
            acc_r = jnp.where(keep, rmin + w * KNN_W, acc_r)
            opt = jnp.where(cols == rmin, -jnp.inf, opt)
        out_s[0, :, :] = acc_s
        out_r[0, :, :] = acc_r

    return kernel


@functools.partial(jax.jit, static_argnames=("similarity",))
def knn_int8_window_topc(qi8, qmeta, q8, meta, act, fmask=None, *,
                         similarity: str = "cosine"):
    """kNN first pass over one partition's int8-quantized shard: per
    2048-doc window, compute every doc's OPTIMISTIC score (int8 MXU dot
    descaled + the tracked quantization bound, pushed through the
    similarity transform — all three transforms are monotone increasing
    in the dot, so per-doc optimism survives them) and keep the window's
    top-KNN_CANDW candidates. The union over windows is a provable
    superset of the true top-k whenever the exact k-th rescore score
    beats the engine's exclusion bound (parallel/knn.py certificate).

    qi8   [QC, dimsP] i8 — quantized queries (dims zero-padded to 128x)
    qmeta [QC, 8] f32 — slots: 0 sq (query int8 step), 1 the scale
          coefficient 0.5*ql1 + dims*sq/4, 2 |q|_2, 3 |q|_2^2,
          4 1/max(|q|_2, 1e-20), 5 sq/2; rest zero
    q8    [nw, dimsP, KNN_W] i8 — window-major stored rows (transposed:
          dims on sublanes, docs on lanes — the MXU contraction layout)
    meta  [nw, 4, KNN_W] f32 — rows (scale, row_l1, nrm, okf); dead pad
          docs carry okf 0 and never surface
    act   [nw, QC, 1] f32 — per-query window activity (IVF probe;
          all-ones when nprobe = 0)
    fmask [nw, QC, KNN_W] i8 or None — per-query doc filter in STORED
          row order (serving candidate masks / live deletes)

    The per-window operands are window-major because the TPU lowering
    wants a block's last two dims (8k, 128m) or the array's own, which a
    1-wide slice of a middle window axis is not.

    Returns (scores [nw, QC, KNN_CANDW] f32, rows [nw, QC, KNN_CANDW]
    i32) — rows are global stored-row ids (w * KNN_W + lane); empty
    slots are (-inf, 0).
    """
    QC, dimsP = qi8.shape
    nw = q8.shape[0]
    kernel = _knn_pass_kernel(similarity, fmask is not None)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.VMEM),             # qi8
        pl.BlockSpec(memory_space=pltpu.VMEM),             # qmeta
        pl.BlockSpec((1, dimsP, KNN_W), lambda w: (w, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 4, KNN_W), lambda w: (w, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, QC, 1), lambda w: (w, 0, 0),
                     memory_space=pltpu.VMEM),             # act column
    ]
    args = [qi8, qmeta, q8, meta, act]
    if fmask is not None:
        in_specs.append(pl.BlockSpec((1, QC, KNN_W), lambda w: (w, 0, 0),
                                     memory_space=pltpu.VMEM))
        args.append(fmask)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(nw,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, QC, KNN_CANDW), lambda w: (w, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, QC, KNN_CANDW), lambda w: (w, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nw, QC, KNN_CANDW), jnp.float32),
            jax.ShapeDtypeStruct((nw, QC, KNN_CANDW), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=_interpret(),
    )
    return fn(*args)
