"""Quantized sharded kNN engine: int8 first pass + exact rescore (PR 19).

Promotes the ad-hoc dense-vector seams (ops/knn.py brute force,
spmd.sharded_knn_topk) into a first-class serving engine able to hold
10M+ vectors per partition in HBM:

  * **int8 first pass with a tracked bound.** Each partition's vector
    matrix is quantized per-row to int8 (one f32 scale per row) and laid
    out window-major ([nw, dimsP, KNN_W] — dims on sublanes, docs on
    lanes), 4x smaller than bf16 and scored by one int8 MXU matmul per
    window (kernels.knn_int8_window_topc). The kernel scores every doc
    OPTIMISTICALLY: descaled dot + the quantization error bound
    (0.5*sq*row_l1 + 0.5*s_r*ql1 + dims*s_r*sq/4, plus a 2^-7*|q||v|
    term covering the reference's bf16 matmul) pushed through the
    similarity transform — all three transforms are monotone increasing
    in the dot, so the per-window top-KNN_CANDW candidates it keeps are
    a provable superset of the true top-k whenever the certificate below
    holds.

  * **Exact f32 rescore, bit-identical.** Survivors (C = k *
    ES_TPU_KNN_RESCORE_MULT per query) are gathered ON HOST from the
    partition's stored f32 rows, uploaded, and rescored in ONE 2D bf16
    gemm by ops.knn.knn_scores, the dense route's own scorer — gathering
    rows commutes with the bf16 cast, and a 2D gemm over gathered rows
    reproduces the corresponding columns of the full dense matmul to the
    last ulp of the backend's gemm tiling (a batched dot_general does
    NOT, which is why all queries' candidates flatten into one
    [Q*C, dims] matrix). The
    exact k-th score is then compared against the exclusion bound
    u_excl = max(optimistic score of the first dropped candidate, the
    per-window truncation tails): strictly above it, the top-k is
    CERTIFIED equal to the f32 brute-force reference (ops.knn.knn_top_k)
    bit-for-bit. Uncertified queries re-run on the dense f32 route
    (lazily uploaded bf16 mirror), so bit-identity holds on EVERY route;
    they are counted in `knn_uncertified`.

  * **IVF coarse pruning (ES_TPU_KNN_NPROBE).** Partitions above
    KNN_IVF_MIN_DOCS build k-means centroids at column-upload time and
    store rows cluster-grouped (a host permutation maps stored row ->
    original ordinal). A first pass probes the nprobe nearest centroids
    and activates only the 2048-doc windows their clusters overlap —
    computed as one [Q, NC] x [NC, nw] matmul, no gathers. nprobe = 0
    (the default) disables pruning and restores exactness; nprobe > 0
    keeps the rescore exact WITHIN the probed windows (recall pinned
    >= 0.99 @ 10 by the differential suite).

  * **Engine contract end to end.** Shards ride the ShardedTurbo
    machinery: stacked [Sp, ...] arrays placed over the mesh 'shard'
    axis (spmd._put_sharded), one fused shard_map dispatch per query
    chunk when a mesh is given, a per-partition solo loop otherwise.
    Regions are charged to the HBM ledger (byte-identical to
    hbm_bytes()), registered in the scrub registry with host-mirror
    repair, and `knn_score` / `knn_rescore` are first-class fault sites:
    a faulted partition falls back to a host-exact f64 scorer (counted
    in `knn_host_fallbacks`) while its peers stay on device, and an
    EngineHealth circuit routes everything host while open.

Merged results follow the serving engine contract: `search_many`
returns (scores [Q, k] f32, parts [Q, k] i32, ords [Q, k] i32) per
batch with the (score desc, partition asc, ord asc) merge cascade;
empty slots are (0, 0, 0) and non-positive scores mark empty — which
makes dot_product vectors with negative similarity unservable here,
same as the BM25 merge convention (the dense executor route still
serves them).
"""

from __future__ import annotations

import functools
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticsearch_tpu.common import (
    faults, hbm_ledger, integrity, metrics, tracing,
)
from elasticsearch_tpu.common.faults import DeviceFaultError, FaultRecord
from elasticsearch_tpu.common.health import EngineHealth
from elasticsearch_tpu.common.settings import knob
from elasticsearch_tpu.ops.knn import knn_scores
from elasticsearch_tpu.parallel.kernels import (
    KNN_CANDW, KNN_W, knn_int8_window_topc,
)
from elasticsearch_tpu.parallel.spmd import _put_sharded, merge_partition_topk

KNN_IVF_MIN_DOCS = 4096    # partitions below this skip the k-means build
KNN_KMEANS_ITERS = 5
KNN_KMEANS_SAMPLE = 65536  # rows sampled for the Lloyd iterations
DEFAULT_QC_SIZES = (8, 32, 128)
_MERGE_ORD_MAX = 1 << 24   # device merge packs ordinals into 24 bits


# --------------------------------------------------------------------------
# node counters (the tpu_knn section of GET /_nodes/stats)
# --------------------------------------------------------------------------

_COUNTS_LOCK = threading.Lock()
_COUNTS = {"knn_queries": 0, "knn_int8_dispatches": 0,
           "knn_rescore_docs": 0, "knn_host_fallbacks": 0,
           "knn_bytes": 0, "knn_uncertified": 0,
           "knn_dense_mask_free": 0, "knn_dense_masked": 0,
           "knn_dense_mask_bytes": 0}              # guarded by: _COUNTS_LOCK

_ENGINES: "weakref.WeakSet[KnnEngine]" = weakref.WeakSet()


def _count(key: str, n: int = 1) -> None:
    with _COUNTS_LOCK:
        _COUNTS[key] += n
    metrics.counter_add(key, n)


def knn_node_stats() -> dict:
    """The `tpu_knn` section of GET /_nodes/stats."""
    with _COUNTS_LOCK:
        out = dict(_COUNTS)
    out["enabled"] = bool(knob("ES_TPU_KNN_INT8"))
    out["nprobe"] = int(knob("ES_TPU_KNN_NPROBE"))
    engines = list(_ENGINES)
    out["engines"] = len(engines)
    out["hbm_bytes"] = sum(e.hbm_bytes() for e in engines)
    return out


def reset_for_tests() -> None:
    with _COUNTS_LOCK:
        for k in _COUNTS:
            _COUNTS[k] = 0


# --------------------------------------------------------------------------
# host-side IVF build: k-means + cluster-grouped row permutation
# --------------------------------------------------------------------------

def _nearest(x: np.ndarray, cent: np.ndarray) -> np.ndarray:
    """Chunked nearest-centroid assignment by squared l2 (the x^2 term is
    constant per row and dropped)."""
    cc = (cent * cent).sum(axis=1)[None, :]
    out = np.empty(len(x), np.int64)
    for o in range(0, len(x), 8192):
        xb = x[o:o + 8192]
        out[o:o + len(xb)] = np.argmin(cc - 2.0 * (xb @ cent.T), axis=1)
    return out


def _kmeans(v: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Centroids + full-row labels. NC ~ sqrt(n) capped at 1024; Lloyd
    iterations run on a fixed-seed sample so the build is deterministic
    and bounded regardless of partition size."""
    n = len(v)
    nc = min(1024, max(8, int(round(n ** 0.5))))
    rng = np.random.default_rng(0x5EED)
    sample = v[rng.choice(n, size=min(n, KNN_KMEANS_SAMPLE), replace=False)]
    cent = sample[rng.choice(len(sample), size=nc, replace=False)].copy()
    for _ in range(KNN_KMEANS_ITERS):
        lab = _nearest(sample, cent)
        sums = np.zeros_like(cent)
        np.add.at(sums, lab, sample)
        cnt = np.bincount(lab, minlength=nc).astype(np.float32)
        nz = cnt > 0
        cent[nz] = sums[nz] / cnt[nz, None]
    return cent, _nearest(v, cent)


# --------------------------------------------------------------------------
# jit programs
# --------------------------------------------------------------------------

def _part_body(qf, qi8, qmeta, q8, meta, cent, cvalid, overlap, fmask,
               similarity: str, C: int, nprobe: int):
    """One partition's first pass: IVF window activity + the int8 kernel
    + candidate selection. Returns (cand_r [Q, C] stored-row ids,
    cand_ok [Q, C], u_excl [Q] exclusion bound, act_frac [Q])."""
    QC = qf.shape[0]
    nw = q8.shape[0]
    if nprobe <= 0:
        act = jnp.ones((nw, QC), jnp.float32)
        frac = jnp.ones((QC,), jnp.float32)
    else:
        dims = qf.shape[1]
        cs = jax.lax.dot_general(
            qf, cent[:, :dims], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [QC, NCp]
        if similarity == "cosine":
            cn = jnp.sqrt(jnp.sum(cent * cent, axis=1))[None, :]
            cs = cs / jnp.maximum(cn, 1e-20)
        elif similarity == "l2_norm":
            qq = jnp.sum(qf * qf, axis=1, keepdims=True)
            cc = jnp.sum(cent * cent, axis=1)[None, :]
            cs = -(qq + cc - 2.0 * cs)
        cs = jnp.where(cvalid[None, :] > 0, cs, -jnp.inf)
        npb = min(int(nprobe), cs.shape[1])
        thr = jax.lax.top_k(cs, npb)[0][:, -1:]
        probed = ((cs >= thr) & (cvalid[None, :] > 0)).astype(jnp.float32)
        hit = jax.lax.dot_general(
            overlap, probed, (((0,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [nw, QC]
        act = (hit > 0).astype(jnp.float32)
        livew = (jnp.max(overlap, axis=0) > 0).astype(jnp.float32)[:, None]
        frac = (jnp.sum(act * livew, axis=0)
                / jnp.maximum(jnp.sum(livew), 1.0))
    # trace-time call: _part_body only ever runs inside the jit-decorated
    # _pass1_solo/_pass1_fused programs, dispatched under device_dispatch
    # ("knn_score") at the call sites below
    out_s, out_r = knn_int8_window_topc(  # tpulint: disable=TPU001
        qi8, qmeta, q8, meta, act[:, :, None], fmask, similarity=similarity)
    fs = jnp.transpose(out_s, (1, 0, 2)).reshape(QC, nw * KNN_CANDW)
    fr = jnp.transpose(out_r, (1, 0, 2)).reshape(QC, nw * KNN_CANDW)
    # 2-key sort = (optimistic desc, stored row asc); -inf empties sink
    ns, nr = jax.lax.sort((-fs, fr), num_keys=2)
    cand_r = nr[:, :C]
    cand_ok = -ns[:, :C] > -jnp.inf
    # a doc missing from the candidate set is bounded by either the first
    # dropped candidate or, if its window truncated at KNN_CANDW, that
    # window's last kept value — both optimistic
    tail = jnp.max(out_s[:, :, KNN_CANDW - 1], axis=0)     # [QC]
    u_excl = jnp.maximum(-ns[:, C], tail)
    return cand_r, cand_ok, u_excl, frac


@functools.partial(jax.jit, static_argnames=("similarity", "C", "nprobe"))
def _pass1_solo(qf, qi8, qmeta, q8, meta, cent, cvalid, overlap, fmask=None,
                *, similarity: str, C: int, nprobe: int):
    return _part_body(qf, qi8, qmeta, q8, meta, cent, cvalid, overlap,
                      fmask, similarity, C, nprobe)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "similarity", "C", "nprobe"))
def _pass1_fused(qf, qi8, qmeta, q8s, metas, cents, cvalids, overlaps,
                 fmasks=None, *, mesh, similarity: str, C: int, nprobe: int):
    """All partitions' first passes in ONE dispatch: stacked shard data
    over the mesh 'shard' axis, queries replicated, vmap over the local
    partition slice."""
    masked = fmasks is not None
    in_specs = [_P_REP, _P_REP, _P_REP, _P_SH, _P_SH, _P_SH, _P_SH, _P_SH]
    if masked:
        in_specs.append(_P_SH)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(_P_SH, _P_SH, _P_SH, _P_SH), check_vma=False)
    def program(qf, qi8, qmeta, q8s, metas, cents, cvalids, overlaps,
                *mrest):
        def one(q8, meta, cent, cvalid, overlap, *fm1):
            return _part_body(qf, qi8, qmeta, q8, meta, cent, cvalid,
                              overlap, fm1[0] if fm1 else None,
                              similarity, C, nprobe)

        args = (q8s, metas, cents, cvalids, overlaps) + tuple(mrest)
        return jax.vmap(one)(*args)

    args = (qf, qi8, qmeta, q8s, metas, cents, cvalids, overlaps)
    if masked:
        args += (fmasks,)
    return program(*args)


_P_REP = P()
_P_SH = P("shard")


@functools.partial(jax.jit, static_argnames=("similarity", "C", "k"))
def _rescore_program(qf, rows, nrmg, okg, ordg, u_excl, *,
                     similarity: str, C: int, k: int):
    """Exact rescore of the gathered candidate rows + the certificate.

    The flattened [Q*C, dims] candidate matrix goes through
    ops.knn.knn_scores itself — the dense reference route's scorer, ONE
    2D bf16 gemm (per-query batching would change f32 accumulation
    order) with the similarity transform in the same place — and each
    query then extracts its own C columns, so a surviving score is the
    reference score on any backend (to the gemm tiling's last ulp)."""
    Q = qf.shape[0]
    sc_all = knn_scores(qf, rows, nrmg.reshape(-1),
                        jnp.ones((Q * C,), bool),
                        similarity=similarity)             # [Q, Q*C]
    idx = (jnp.arange(Q, dtype=jnp.int32)[:, None] * C
           + jnp.arange(C, dtype=jnp.int32)[None, :])
    sc = jnp.take_along_axis(sc_all, idx, axis=1)          # [Q, C]
    sc = jnp.where(okg, sc, -jnp.inf)
    ns, no = jax.lax.sort((-sc, ordg), num_keys=2)
    top_s = -ns[:, :k]
    top_o = no[:, :k]
    # STRICT: a tie at the bound could hide an excluded doc with an equal
    # exact score and a lower ordinal, which the reference would prefer
    certified = (top_s[:, k - 1] > u_excl) | jnp.isneginf(u_excl)
    valid = top_s > -jnp.inf
    return (jnp.where(valid, top_s, 0.0),
            jnp.where(valid, top_o, 0), certified)


def _filter_bits_width(n: int) -> int:
    """Bytes a query of `_pack_filter`'s rows: n >= 1 bits, rounded up to
    whole 128-byte lanes so the eight bit planes unpack lane-aligned."""
    return -(-n // 1024) * 128


_PLANE_SHIFTS = np.arange(8, dtype=np.uint8)[:, None]


def _pack_filter(flt: np.ndarray, nb: int) -> np.ndarray:
    """One query's [n] bool doc filter as [nb] uint8, bit-PLANE order: doc
    d is bit d // nb of byte d % nb, so the device unpacks with eight
    shifts and a concatenation along the doc axis (no [.., 8] minor
    dimension to re-tile). Bits past n are 0."""
    planes = np.zeros(8 * nb, np.uint8)
    planes[:len(flt)] = flt
    # eight contiguous shifts + ors: np.packbits down axis 0 reads the
    # planes a strided byte at a time and takes eight times as long
    return np.bitwise_or.reduce(planes.reshape(8, nb) << _PLANE_SHIFTS,
                                axis=0)


@functools.partial(jax.jit, static_argnames=("similarity", "k"))
def _dense_topk(qf, vectors, norms, ok, fbits=None, *,
                similarity: str, k: int):
    """The f32 brute-force reference route (ES_TPU_KNN_INT8=0 A/B and
    uncertified re-runs): ops.knn.knn_scores over the resident `ok` =
    exists & live row + (only where a query of the chunk carries one) the
    per-query filters, `_pack_filter` rows [QC, nb] u8 unpacked here +
    top_k — bit-identical to knn_top_k under the mask ok & filter."""
    sc = knn_scores(qf, vectors, norms, ok, similarity=similarity)
    if fbits is not None:
        keep = jnp.concatenate(
            [(fbits >> b) & 1 for b in range(8)], axis=1)[:, :sc.shape[1]]
        sc = jnp.where(keep != 0, sc, -jnp.inf)
    ts, to = jax.lax.top_k(sc, k)
    valid = ts > -jnp.inf
    return jnp.where(valid, ts, 0.0), jnp.where(valid, to, 0)


# --------------------------------------------------------------------------
# the work unit
# --------------------------------------------------------------------------

class KnnWork:
    """One kNN query riding a serving dispatch: the query vector plus an
    optional per-partition doc filter (bool mask over the partition's
    ordinals — e.g. the BM25 sweep's candidate mask in the fused hybrid
    route; None = unfiltered)."""

    __slots__ = ("vector", "filters")

    def __init__(self, vector: np.ndarray,
                 filters: Optional[Sequence[Optional[np.ndarray]]] = None):
        self.vector = np.asarray(vector, np.float32)
        self.filters = filters


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

# the steps of one engine call (tracing.steps): each takes one histogram
# observation per call, see common/metrics.py
DISPATCH_STEPS = metrics.DISPATCH_TOP_STEPS + (
    "dispatch.mask", "dispatch.rescore", "dispatch.dense_rerun")


class KnnEngine:
    """Quantized sharded kNN over one vector field's partitions.

    columns: per-partition vector columns (index.segment.VectorColumn
    contract: .vectors [n, dims], .norms [n], .exists [n], .similarity).
    lives: optional per-partition live masks (deletes). mesh: a spmd
    (dp=1, shard) mesh fuses all partitions into one dispatch per chunk;
    None runs the per-partition solo loop."""

    kind = "knn"

    def __init__(self, columns: Sequence, lives: Optional[Sequence] = None,
                 mesh=None, qc_sizes: Sequence[int] = DEFAULT_QC_SIZES):
        cols = list(columns)
        if not cols:
            raise ValueError("KnnEngine needs at least one partition")
        sims = {c.similarity for c in cols}
        if len(sims) != 1:
            raise ValueError(f"mixed similarities {sims}")
        self.similarity = cols[0].similarity
        S = len(cols)
        self.S = S
        self.dims = int(cols[0].vectors.shape[1])
        self.dimsP = -(-self.dims // 128) * 128
        fused = mesh is not None and S > 1
        if fused and mesh.shape.get("dp", 1) != 1:
            raise ValueError("fused kNN shards partitions over 'shard' only")
        self._fused = fused
        self.mesh = mesh if fused else None
        G = mesh.shape["shard"] if fused else 1
        self.devices = G
        self.Sp = -(-S // G) * G
        self.qc_sizes = tuple(sorted({int(s) for s in qc_sizes}))

        self.n_docs: List[int] = []
        self._vecs: List[np.ndarray] = []     # stored f32 rows (rescore src)
        self._norms: List[np.ndarray] = []    # RAW row norms (l2 rescore)
        self._exists: List[np.ndarray] = []
        self._ok: List[np.ndarray] = []       # exists & live
        self._perm: List[np.ndarray] = []     # [nw*KNN_W] stored -> ord
        preps = []
        for i, col in enumerate(cols):
            n = int(col.vectors.shape[0])
            v = np.ascontiguousarray(col.vectors.astype(np.float32))
            norms = np.asarray(col.norms, np.float32)
            if self.similarity == "cosine":
                # the SAME host expression as Segment.device('vec:') /
                # build_stacked_knn — bit-identity depends on it
                v = v / np.maximum(norms, 1e-20)[:, None]
            exists = np.asarray(col.exists, bool)
            live = (np.asarray(lives[i], bool)
                    if lives is not None and lives[i] is not None
                    else np.ones(n, bool))
            if n >= KNN_IVF_MIN_DOCS:
                with tracing.phase("engine_build.kmeans", partition=i):
                    cent, labels = _kmeans(v)
                order = np.argsort(labels, kind="stable")
                counts = np.bincount(labels, minlength=len(cent))
            else:
                # no IVF: one dummy centroid covering every window, so a
                # probed first pass degrades to the exact sweep here
                cent = np.zeros((1, self.dims), np.float32)
                order = np.arange(n)
                counts = np.asarray([n])
            self.n_docs.append(n)
            self._vecs.append(v)
            self._norms.append(norms)
            self._exists.append(exists)
            self._ok.append(exists & live)
            preps.append((cent, order, counts))

        self.nw = max(1, max(-(-n // KNN_W) for n in self.n_docs))
        self.NCp = -(-max(len(c) for c, _, _ in preps) // 8) * 8
        with tracing.phase("engine_build.int8_windows", partitions=S):
            DPg = self.nw * KNN_W
            q8h = np.zeros((self.Sp, self.nw, self.dimsP, KNN_W), np.int8)
            # window-major [nw, 4, KNN_W] per partition: the kernel fetches one
            # window's four meta rows as a block
            metah = np.zeros((self.Sp, self.nw, 4, KNN_W), np.float32)
            centh = np.zeros((self.Sp, self.NCp, self.dimsP), np.float32)
            cvalh = np.zeros((self.Sp, self.NCp), np.float32)
            ovh = np.zeros((self.Sp, self.NCp, self.nw), np.float32)
            for i, (cent, order, counts) in enumerate(preps):
                n = self.n_docs[i]
                perm = np.zeros(DPg, np.int32)
                perm[:n] = order
                self._perm.append(perm)
                nc = len(cent)
                centh[i, :nc, :self.dims] = cent
                cvalh[i, :nc] = 1.0
                starts = np.concatenate([[0], np.cumsum(counts)])
                for c in range(nc):
                    s0, s1 = int(starts[c]), int(starts[c + 1])
                    if s1 > s0:
                        ovh[i, c, s0 // KNN_W:(s1 - 1) // KNN_W + 1] = 1.0
                if n == 0:
                    continue
                vi = self._vecs[i][order]                      # stored order
                s_r = np.maximum(np.abs(vi).max(axis=1), 1e-12) / 127.0
                vi8 = np.clip(np.round(vi / s_r[:, None]), -127, 127) \
                    .astype(np.int8)
                row_l1 = s_r * np.abs(vi8.astype(np.float32)).sum(axis=1)
                nrm = np.linalg.norm(vi, axis=1).astype(np.float32)
                okf = self._ok[i][order].astype(np.float32)
                for w in range(-(-n // KNN_W)):
                    lo, hi = w * KNN_W, min((w + 1) * KNN_W, n)
                    q8h[i, w, :self.dims, :hi - lo] = vi8[lo:hi].T
                    metah[i, w, 0, :hi - lo] = s_r[lo:hi].astype(np.float32)
                    metah[i, w, 1, :hi - lo] = row_l1[lo:hi].astype(np.float32)
                    metah[i, w, 2, :hi - lo] = nrm[lo:hi]
                    metah[i, w, 3, :hi - lo] = okf[lo:hi]
        self._q8_host = q8h
        self._meta_host = metah
        self._cent_host = centh
        self._cvalid_host = cvalh
        self._overlap_host = ovh
        self._sharding = (NamedSharding(self.mesh, P("shard"))
                          if self._fused else None)
        # translation only (device_errors, no fault_point): construction
        # runs outside the serving containment ladder
        with tracing.phase("engine_build.upload", bytes=q8h.nbytes), \
                faults.device_errors("column_upload"):
            self.d_q8 = _put_sharded(q8h, self.mesh)
            self.d_meta = _put_sharded(metah, self.mesh)
            self.d_cent = _put_sharded(centh, self.mesh)
            self.d_cvalid = _put_sharded(cvalh, self.mesh)
            self.d_overlap = _put_sharded(ovh, self.mesh)
        self._dense: List[Optional[tuple]] = [None] * S

        self.health = EngineHealth("knn")
        self._hbm = hbm_ledger.register_engine(self, "knn", devices=G)
        self._register_hbm_regions()
        self._register_scrub_regions()
        integrity.attach_scrub_health(self, self.health)
        _count("knn_bytes", self.hbm_bytes())
        _ENGINES.add(self)

    # ---------------- residency / integrity ----------------

    def _mirror_bytes(self) -> int:
        return sum(sum(a.nbytes for a in d)
                   for d in self._dense if d is not None)

    def _register_hbm_regions(self) -> None:
        self._hbm.set_region("knn_shards", self.d_q8.nbytes)
        self._hbm.set_region("knn_meta", self.d_meta.nbytes)
        self._hbm.set_region("knn_centroids",
                             self.d_cent.nbytes + self.d_cvalid.nbytes
                             + self.d_overlap.nbytes)
        self._hbm.set_region("knn_dense_mirror", self._mirror_bytes())

    def hbm_bytes(self) -> int:
        return (self.d_q8.nbytes + self.d_meta.nbytes + self.d_cent.nbytes
                + self.d_cvalid.nbytes + self.d_overlap.nbytes
                + self._mirror_bytes())

    def _register_scrub_regions(self) -> None:
        integrity.register_scrub_region(
            self, "knn_shards", lambda o: o.d_q8,
            expected=lambda o: o._q8_host,
            repair=lambda o: o._repair_shards())
        integrity.register_scrub_region(
            self, "knn_meta", lambda o: o.d_meta,
            expected=lambda o: o._meta_host,
            repair=lambda o: o._repair_meta())
        integrity.register_scrub_region(
            self, "knn_centroids", lambda o: o.d_cent,
            expected=lambda o: o._cent_host,
            repair=lambda o: o._repair_centroids())

    def _repair_shards(self) -> None:
        # translation only (device_errors, no fault_point): repairs must
        # not be separately injectable rungs
        with faults.device_errors("column_upload"):
            self.d_q8 = _put_sharded(self._q8_host, self.mesh)

    def _repair_meta(self) -> None:
        with faults.device_errors("column_upload"):
            self.d_meta = _put_sharded(self._meta_host, self.mesh)

    def _repair_centroids(self) -> None:
        with faults.device_errors("column_upload"):
            self.d_cent = _put_sharded(self._cent_host, self.mesh)
            self.d_cvalid = _put_sharded(self._cvalid_host, self.mesh)
            self.d_overlap = _put_sharded(self._overlap_host, self.mesh)

    def _ensure_dense(self, i: int) -> None:
        """Lazily upload partition i's bf16 mirror for the dense f32
        brute-force route (the INT8=0 A/B path and uncertified re-runs).
        device cast of the SAME host f32 rows the reference uploads —
        bitwise-equal bf16 values. The third array is the `ok` = exists &
        live row: the route's whole mask unless a query carries a filter,
        kept current by `set_live`."""
        if self._dense[i] is not None:
            return
        with tracing.phase("engine_build.dense_mirror", partition=i), \
                faults.device_errors("column_upload"):
            trip = (jnp.asarray(self._vecs[i]).astype(jnp.bfloat16),
                    jnp.asarray(self._norms[i]),
                    jnp.asarray(self._ok[i]))
        self._dense[i] = trip
        _count("knn_bytes", sum(a.nbytes for a in trip))
        self._register_hbm_regions()

    def set_live(self, i: int, live: np.ndarray) -> None:
        """Refresh one partition's live mask (deletes): host meta update
        + one device re-upload of the okf row (and of the dense mirror's
        `ok` row where the mirror exists), under the column_upload
        containment site like every other engine refresh."""
        n = self.n_docs[i]
        ok = self._exists[i] & np.asarray(live, bool)
        self._ok[i] = ok
        okf = np.zeros(self.nw * KNN_W, np.float32)
        if n:
            okf[:n] = ok[self._perm[i][:n]].astype(np.float32)
        okw = okf.reshape(self.nw, KNN_W)
        self._meta_host[i, :, 3] = okw
        with faults.device_dispatch("column_upload", part=i):
            upd = self.d_meta.at[i, :, 3].set(jnp.asarray(okw))
            if self._fused:
                upd = jax.device_put(upd, self._sharding)
            self.d_meta = upd
            if self._dense[i] is not None:
                self._dense[i] = self._dense[i][:2] + (jnp.asarray(ok),)

    # ---------------- scheduler hooks ----------------

    def extend_qc_sizes(self, sizes) -> None:
        self.qc_sizes = tuple(sorted(set(self.qc_sizes)
                                     | {int(s) for s in sizes}))
        hbm_ledger.note_primed("knn", self.qc_sizes)
        hbm_ledger.note_primed("knn_dense", self.qc_sizes)

    # ---------------- host tiers ----------------

    def _host_exact(self, i: int, wk: KnnWork, k: int):
        """f64 host-exact scorer — the containment fallback when a
        partition's device dispatch faults. Correctness-equal (not
        bitwise: numpy BLAS f64 vs device bf16)."""
        n = self.n_docs[i]
        if n == 0:
            return np.zeros(k, np.float32), np.zeros(k, np.int32)
        q = wk.vector.astype(np.float64)
        dots = self._vecs[i].astype(np.float64) @ q
        if self.similarity == "cosine":
            sc = (1.0 + dots / max(float(np.linalg.norm(q)), 1e-20)) / 2.0
        elif self.similarity == "dot_product":
            sc = (1.0 + dots) / 2.0
        else:
            nrm = self._norms[i].astype(np.float64)
            d2 = np.maximum(float(q @ q) + nrm * nrm - 2.0 * dots, 0.0)
            sc = 1.0 / (1.0 + np.sqrt(d2))
        mask = self._ok[i].copy()
        if wk.filters is not None and wk.filters[i] is not None:
            mask &= np.asarray(wk.filters[i], bool)
        sc = np.where(mask, sc, -np.inf)
        order = np.lexsort((np.arange(n), -sc))[:k]
        order = order[sc[order] > -np.inf]
        s = np.zeros(k, np.float32)
        o = np.zeros(k, np.int32)
        s[:len(order)] = sc[order]
        o[:len(order)] = order
        return s, o

    def _host_chunk(self, i: int, chunk, k: int):
        s = np.zeros((len(chunk), k), np.float32)
        o = np.zeros((len(chunk), k), np.int32)
        for j, wk in enumerate(chunk):
            s[j], o[j] = self._host_exact(i, wk, k)
        return s, o

    # ---------------- device routes ----------------

    def _quantize_queries(self, qf: np.ndarray):
        QC, dims = qf.shape
        sq = np.maximum(np.abs(qf).max(axis=1), 1e-12) / 127.0
        qi8 = np.zeros((QC, self.dimsP), np.int8)
        qi8[:, :dims] = np.clip(np.round(qf / sq[:, None]), -127, 127)
        ql1 = sq * np.abs(qi8.astype(np.float32)).sum(axis=1)
        qn = np.linalg.norm(qf, axis=1)
        qm = np.zeros((QC, 8), np.float32)
        qm[:, 0] = sq
        qm[:, 1] = 0.5 * ql1 + dims * sq / 4.0
        qm[:, 2] = qn
        qm[:, 3] = qn * qn
        qm[:, 4] = 1.0 / np.maximum(qn, 1e-20)
        qm[:, 5] = 0.5 * sq
        return qi8, qm

    def _filter_mask(self, i: int, chunk, QC: int) -> np.ndarray:
        """Per-query doc filters permuted to STORED row order, window-
        major [nw, QC, KNN_W] i8 (the kernel's block layout). Pad rows may
        alias doc 0 through the pad permutation entries — the kernel's okf
        gate keeps them dead regardless."""
        n = self.n_docs[i]
        fm = np.ones((self.nw, QC, KNN_W), np.int8)
        perm_c = np.minimum(self._perm[i], max(n - 1, 0))
        for j, wk in enumerate(chunk):
            flt = wk.filters[i] if wk.filters is not None else None
            if flt is None or n == 0:
                continue
            fm[:, j, :] = np.asarray(flt, bool)[perm_c].reshape(
                self.nw, KNN_W)
        return fm

    def _dense_chunk(self, i: int, qfd, chunk, k: int):
        """The f32 brute-force route for one partition (solo dispatch):
        (scores, ords), each [len(chunk), k]. `qfd` is the
        padded [QC, dims] query matrix, on the device already. The mask
        stays there too: liveness is the mirror's resident `ok` row, and
        only a chunk in which some query filters this partition uploads
        anything — the filters, as bits, [QC, nb] u8 whatever the mix."""
        n = self.n_docs[i]
        if n == 0:
            return (np.zeros((len(chunk), k), np.float32),
                    np.zeros((len(chunk), k), np.int32))
        self._ensure_dense(i)
        fbits = None
        flts = [wk.filters[i] if wk.filters is not None else None
                for wk in chunk]
        if any(f is not None for f in flts):
            nb = _filter_bits_width(n)
            fbits = np.full((qfd.shape[0], nb), 0xFF, np.uint8)
            for j, f in enumerate(flts):
                if f is not None:
                    fbits[j] = _pack_filter(np.asarray(f, bool), nb)
            _count("knn_dense_masked")
            _count("knn_dense_mask_bytes", fbits.nbytes)
        else:
            _count("knn_dense_mask_free")
        v, nrm, ok = self._dense[i]
        with faults.device_dispatch("knn_score", part=i):
            ts, to = _dense_topk(
                qfd, v, nrm, ok,
                None if fbits is None else jnp.asarray(fbits),
                similarity=self.similarity, k=k)
            return (np.asarray(ts)[:len(chunk)],
                    np.asarray(to)[:len(chunk)])

    def _run_chunk(self, chunk, QC: int, k: int, local_faults: List,
                   check=None):
        """One padded query chunk across all partitions. Returns
        (s [S, n, k], o [S, n, k]) per-partition numpy results."""
        n = len(chunk)
        S = self.S
        use_int8 = bool(knob("ES_TPU_KNN_INT8"))
        nprobe = max(0, int(knob("ES_TPU_KNN_NPROBE")))
        mult = max(1, int(knob("ES_TPU_KNN_RESCORE_MULT")))
        C = min(k * mult, self.nw * KNN_CANDW - 1)
        s_out = np.zeros((S, n, k), np.float32)
        o_out = np.zeros((S, n, k), np.int32)
        with tracing.phase("dispatch.prep", qc=QC):
            qf = np.zeros((QC, self.dims), np.float32)
            for j, wk in enumerate(chunk):
                qf[j, :len(wk.vector)] = wk.vector
            if use_int8 and k <= C:
                qi8, qmeta = self._quantize_queries(qf)
            qfd = jnp.asarray(qf)

        if not use_int8 or k > C:
            # the f32 brute-force A/B path, verbatim per partition: one
            # `dispatch.launch` holds its calls and their fetches
            t0 = time.monotonic()
            first = hbm_ledger.note_dispatch("knn_dense", QC)
            with tracing.phase("dispatch.launch", qc=QC, route="dense"):
                for i in range(S):
                    try:
                        s_out[i], o_out[i] = self._dense_chunk(
                            i, qfd, chunk, k)
                    except DeviceFaultError as e:
                        local_faults.append(
                            FaultRecord.from_error(e, partition=i))
                        _count("knn_host_fallbacks", n)
                        self.health.record_fallback(n)
                        s_out[i], o_out[i] = self._host_chunk(i, chunk, k)
            if first:
                hbm_ledger.note_compile_done(
                    "knn_dense", QC, time.monotonic() - t0)
            return s_out, o_out

        _count("knn_int8_dispatches", 1)
        masked = any(wk.filters is not None for wk in chunk)
        t0 = time.monotonic()
        first = hbm_ledger.note_dispatch("knn", QC)
        pass1: Dict[int, tuple] = {}
        failed: Dict[int, DeviceFaultError] = {}
        if self._fused:
            fmasks = None
            if masked:
                with tracing.phase("dispatch.prep", qc=QC), \
                        tracing.phase("dispatch.mask", partitions=S):
                    fmasks = np.zeros((self.Sp, self.nw, QC, KNN_W), np.int8)
                    for i in range(S):
                        fmasks[i] = self._filter_mask(i, chunk, QC)
                    fmasks = jnp.asarray(fmasks)
            try:
                with faults.device_dispatch("knn_score"):
                    # the call returning, not the pass: the launch is
                    # async, and holds trace + lower + compile when the
                    # program is new
                    with tracing.phase("dispatch.launch", qc=QC,
                                       partitions=S):
                        rr = _pass1_fused(
                            qfd, jnp.asarray(qi8), jnp.asarray(qmeta),
                            self.d_q8, self.d_meta, self.d_cent,
                            self.d_cvalid, self.d_overlap, fmasks,
                            mesh=self.mesh, similarity=self.similarity,
                            C=C, nprobe=nprobe)
                    with tracing.phase("dispatch.device_wait"):
                        cr, cok, ux, fr = (np.asarray(a) for a in rr)
                for i in range(S):
                    pass1[i] = (cr[i], cok[i], ux[i], fr[i])
            except DeviceFaultError as e:
                # fused fault: the whole chunk host-routes, every
                # partition — mirror ShardedTurbo containment
                local_faults.append(FaultRecord.from_error(e))
                _count("knn_host_fallbacks", n * S)
                self.health.record_fallback(n * S)
                with tracing.phase("dispatch.finish", host_tier=True):
                    for i in range(S):
                        s_out[i], o_out[i] = self._host_chunk(i, chunk, k)
                if first:
                    hbm_ledger.note_compile_done(
                        "knn", QC, time.monotonic() - t0)
                return s_out, o_out
        else:
            for i in range(S):
                fmask = None
                if masked:
                    with tracing.phase("dispatch.prep", qc=QC), \
                            tracing.phase("dispatch.mask", partition=i):
                        fmask = jnp.asarray(self._filter_mask(i, chunk, QC))
                try:
                    with faults.device_dispatch("knn_score", part=i):
                        with tracing.phase("dispatch.launch", qc=QC,
                                           partition=i):
                            rr = _pass1_solo(
                                qfd, jnp.asarray(qi8), jnp.asarray(qmeta),
                                self.d_q8[i], self.d_meta[i], self.d_cent[i],
                                self.d_cvalid[i], self.d_overlap[i], fmask,
                                similarity=self.similarity, C=C,
                                nprobe=nprobe)
                        with tracing.phase("dispatch.device_wait"):
                            pass1[i] = tuple(np.asarray(a) for a in rr)
                except DeviceFaultError as e:
                    failed[i] = e
        if first:
            hbm_ledger.note_compile_done("knn", QC, time.monotonic() - t0)

        with tracing.phase("dispatch.finish", queries=n):
            for i in range(S):
                if check is not None:
                    check()
                if i in failed:
                    local_faults.append(
                        FaultRecord.from_error(failed[i], partition=i))
                    _count("knn_host_fallbacks", n)
                    self.health.record_fallback(n)
                    s_out[i], o_out[i] = self._host_chunk(i, chunk, k)
                    continue
                if self.n_docs[i] == 0:
                    continue
                cand_r, cand_ok, u_excl, _frac = pass1[i]
                ords = self._perm[i][cand_r]
                ords = np.where(cand_ok, ords, 0).astype(np.int32)
                _count("knn_rescore_docs", int(cand_ok[:n].sum()))
                try:
                    # host row gather, the rescore program, its fetch
                    with tracing.phase("dispatch.rescore", partition=i):
                        rows = self._vecs[i][ords.reshape(-1)]
                        nrmg = self._norms[i][ords]
                        with faults.device_dispatch("knn_rescore", part=i):
                            ts, to, cert = _rescore_program(
                                qfd, jnp.asarray(rows), jnp.asarray(nrmg),
                                jnp.asarray(cand_ok), jnp.asarray(ords),
                                jnp.asarray(u_excl),
                                similarity=self.similarity, C=C, k=k)
                            ts, to, cert = (np.asarray(ts), np.asarray(to),
                                            np.asarray(cert))
                except DeviceFaultError as e:
                    local_faults.append(FaultRecord.from_error(e, partition=i))
                    _count("knn_host_fallbacks", n)
                    self.health.record_fallback(n)
                    s_out[i], o_out[i] = self._host_chunk(i, chunk, k)
                    continue
                s_out[i], o_out[i] = ts[:n], to[:n]
                bad = np.nonzero(~cert[:n])[0]
                if len(bad):
                    # certificate miss: the candidate set may not cover the
                    # true top-k — re-run those queries on the dense route,
                    # which restores bit-identity unconditionally
                    _count("knn_uncertified", len(bad))
                    try:
                        with tracing.phase("dispatch.dense_rerun", partition=i,
                                           queries=len(bad)):
                            ds, do = self._dense_chunk(i, qfd, chunk, k)
                        s_out[i][bad] = ds[bad]
                        o_out[i][bad] = do[bad]
                    except DeviceFaultError as e:
                        local_faults.append(
                            FaultRecord.from_error(e, partition=i))
                        _count("knn_host_fallbacks", len(bad))
                        self.health.record_fallback(len(bad))
                        hs, ho = self._host_chunk(i, chunk, k)
                        s_out[i][bad] = hs[bad]
                        o_out[i][bad] = ho[bad]
        return s_out, o_out

    # ---------------- merge ----------------

    def _merge(self, s_all: np.ndarray, o_all: np.ndarray, k: int):
        """(score desc, partition asc, ord asc) merge of the per-partition
        top-k — on device when fused (merge_topk kernel twin), host
        lexsort otherwise; both orders are identical by construction."""
        if (self._fused and self.S > 1
                and max(self.n_docs) < _MERGE_ORD_MAX):
            try:
                with faults.device_dispatch("merge_kernel"):
                    return merge_partition_topk(self.mesh, s_all, o_all, k)
            except DeviceFaultError:
                pass        # host merge is bit-identical anyway
        S, Q, kk = s_all.shape
        ms = np.zeros((Q, k), np.float32)
        mp = np.zeros((Q, k), np.int32)
        mo = np.zeros((Q, k), np.int32)
        parts = np.repeat(np.arange(S, dtype=np.int32), kk)
        for qi in range(Q):
            s = s_all[:, qi, :].ravel()
            o = o_all[:, qi, :].ravel()
            keep = s > 0
            s, o, p = s[keep], o[keep], parts[keep]
            order = np.lexsort((o, p, -s))[:k]
            ms[qi, :len(order)] = s[order]
            mp[qi, :len(order)] = p[order]
            mo[qi, :len(order)] = o[order]
        return ms, mp, mo

    # ---------------- the serving entry ----------------

    def search_many(self, batches: Sequence[List[KnnWork]], k: int = 10,
                    check=None, fault_log=None):
        """Per batch: merged (scores [Q, k] f32, parts [Q, k] i32,
        ords [Q, k] i32); empty slots are (0, 0, 0). Chunks ride the
        qc_sizes bucket ladder; contained faults append FaultRecords
        and feed the health circuit (open circuit = host tier)."""
        with tracing.steps(DISPATCH_STEPS):
            return self._search_many(batches, k, check, fault_log)

    def _search_many(self, batches, k, check, fault_log):
        spans = []
        flat: List[KnnWork] = []
        for b in batches:
            spans.append((len(flat), len(b)))
            flat.extend(b)
        Q = len(flat)
        if Q == 0:
            return [(np.zeros((nn, k), np.float32),
                     np.zeros((nn, k), np.int32),
                     np.zeros((nn, k), np.int32)) for _, nn in spans]
        _count("knn_queries", Q)
        local_faults: List[FaultRecord] = []
        s_all = np.zeros((self.S, Q, k), np.float32)
        o_all = np.zeros((self.S, Q, k), np.int32)
        if not self.health.allow_device():
            # circuit open: the whole batch serves from the host tier
            _count("knn_host_fallbacks", Q * self.S)
            self.health.record_fallback(Q * self.S)
            with tracing.phase("dispatch.finish", host_tier=True):
                for i in range(self.S):
                    s_all[i], o_all[i] = self._host_chunk(i, flat, k)
                ms, mp, mo = self._merge(s_all, o_all, k)
        else:
            off = 0
            while off < Q:
                rem = Q - off
                take = next((s for s in self.qc_sizes if s >= rem),
                            self.qc_sizes[-1])
                chunk = flat[off:off + take]
                if check is not None:
                    check()
                cs, co = self._run_chunk(chunk, take, k, local_faults,
                                         check=check)
                s_all[:, off:off + len(chunk)] = cs
                o_all[:, off:off + len(chunk)] = co
                off += len(chunk)
            if local_faults:
                self.health.record_fault(local_faults[-1].error)
            else:
                self.health.record_success()
            with tracing.phase("dispatch.finish", merge=self.S):
                ms, mp, mo = self._merge(s_all, o_all, k)
        if fault_log is not None:
            fault_log.extend(local_faults)
        return [(ms[o:o + nn], mp[o:o + nn], mo[o:o + nn])
                for o, nn in spans]

    def stats(self) -> dict:
        out = {"partitions": self.S, "fused": int(self._fused),
               "nw": self.nw, "hbm_bytes": self.hbm_bytes()}
        out.update(self.health.flat_stats())
        return out


def build_knn_engine(columns: Sequence, lives: Optional[Sequence] = None,
                     mesh=None) -> KnnEngine:
    """Constructor seam for serving: one engine per (snapshot, field)."""
    return KnnEngine(columns, lives=lives, mesh=mesh)
