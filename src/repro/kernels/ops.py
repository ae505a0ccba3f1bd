"""Jit'd public wrappers for the Pallas kernels with implementation dispatch.

``impl``:
  * ``"numpy"``   — pure-host port (no device round-trip; exact keys for
                    the join, float32 math for the distance).
  * ``"ref"``     — pure-jnp oracle, compiled by XLA for the backend.
  * ``"pallas"``  — the Pallas kernel.  What it runs is fixed per backend by
                    :data:`PALLAS_RUNS`: on the CPU every kernel runs in
                    Pallas interpret mode (a correctness tool, not a
                    performance path); on TPU every kernel runs
                    Mosaic-compiled except the hash join, which runs its
                    ``ref`` path there (see ``docs/kernels.md``).

Every public op resolves ``impl`` through a ``resolve_*_impl`` knob
(``QUIP_<OP>_IMPL`` env) or forwards it to one that does — the quiplint
kernel-parity pass (``python -m repro.analysis``) enforces this triple.
Unset, bloom probe and masked distance take :func:`default_impl` (Pallas
on TPU, ref on CPU); join, neighbour aggregation and segment reduction
default to numpy in the engine.  A backend that is neither CPU nor TPU
raises: no path silently stands in for another.  :data:`KERNEL_CALLS`
tallies which implementation each call actually ran.
"""

from __future__ import annotations

import collections
import functools
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis.lockcheck import make_lock
from repro.core.env import env_choice
from repro.kernels import ref as _ref
from repro.kernels.bloom_probe import bloom_probe_pallas
from repro.kernels.hash_join import (
    hash_join_build_pallas,
    hash_join_probe_pallas,
    table_log2cap,
)
from repro.kernels.hashing import MULTIPLIERS, OFFSETS, fold64
from repro.kernels.knn_distance import masked_distance_pallas
from repro.kernels.neighbor_agg import neighbor_mean_pallas, neighbor_mode_pallas
from repro.kernels.segment_ops import segment_reduce_pallas
from repro.obs.trace import NULL_SPAN

__all__ = [
    "KERNEL_CALLS",
    "PALLAS_RUNS",
    "bloom_probe",
    "hash_join_match",
    "masked_distance",
    "masked_knn",
    "neighbor_aggregate",
    "segment_reduce",
    "default_impl",
    "resolve_bloom_impl",
    "resolve_dist_impl",
    "resolve_join_impl",
    "resolve_knn_impl",
    "resolve_segment_impl",
]

#: The unset kernel choice per backend.
_DEFAULT_IMPL = {"cpu": "ref", "tpu": "pallas"}

#: What ``impl="pallas"`` runs, per backend and op.  Mosaic lowers no
#: vector gather from a VMEM array and no scalar-indexed update of one, and
#: the hash-join build and probe need both, so on TPU the join runs its jnp
#: ``ref`` path; ``tests/test_tpu_compile.py`` holds a strict-xfail compile
#: test for each of the two kernels.
PALLAS_RUNS: Dict[str, Dict[str, str]] = {
    "cpu": {"bloom": "pallas", "dist": "pallas", "join": "pallas",
            "knn": "pallas", "segment": "pallas"},
    "tpu": {"bloom": "pallas", "dist": "pallas", "join": "ref",
            "knn": "pallas", "segment": "pallas"},
}


def _backend() -> str:
    backend = jax.default_backend()
    if backend not in _DEFAULT_IMPL:
        raise RuntimeError(
            f"no kernel dispatch for JAX backend {backend!r} "
            f"(known: {sorted(_DEFAULT_IMPL)})"
        )
    return backend


def default_impl() -> str:
    """The unset kernel choice for this backend: Pallas on TPU, ref on CPU."""
    return _DEFAULT_IMPL[_backend()]


def _interpret() -> bool:
    """Pallas interpret mode runs on the CPU backend only."""
    return _backend() == "cpu"


class KernelCalls:
    """Process-wide tally of kernel-layer calls, keyed
    ``"<op>/<impl that ran>"`` with ``pallas-interpret`` for interpret
    mode — the record of what actually ran on which path."""

    def __init__(self):
        self._lock = make_lock("KernelCalls._lock")
        self._counts: collections.Counter = collections.Counter()  # guarded-by: _lock

    @staticmethod
    def key(op: str, impl: str) -> str:
        if impl == "pallas" and _interpret():
            impl = "pallas-interpret"
        return f"{op}/{impl}"

    def record(self, op: str, impl: str) -> None:
        key = self.key(op, impl)
        with self._lock:
            self._counts[key] += 1

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


KERNEL_CALLS = KernelCalls()

_HOST_IMPLS = ("numpy", "ref", "pallas")


def _bucket(n: int, floor: int) -> int:
    """Smallest power of two >= ``n`` (at least ``floor``).  Device paths
    pad row counts to these sizes so that jit compiles a few shapes, not
    one per ragged batch."""
    return max(floor, 1 << max(n - 1, 0).bit_length())


def _pad_rows(x: np.ndarray, rows: int, value=0) -> np.ndarray:
    pad = rows - x.shape[0]
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, widths, constant_values=value)


def _runs(op: str, impl: str) -> str:
    """Validate a chosen ``impl`` and map ``pallas`` to what runs here
    (:data:`PALLAS_RUNS`)."""
    if impl not in _HOST_IMPLS:
        raise ValueError(f"unknown {op} impl {impl!r}")
    return PALLAS_RUNS[_backend()][op] if impl == "pallas" else impl


def resolve_bloom_impl(impl: Optional[str] = None) -> str:
    """Bloom-probe dispatch: explicit ``impl`` > ``QUIP_BLOOM_IMPL`` env >
    the backend default (Pallas on TPU, ref on CPU)."""
    if impl is None:
        impl = env_choice("QUIP_BLOOM_IMPL", _HOST_IMPLS, "auto")
        impl = default_impl() if impl == "auto" else impl
    return _runs("bloom", impl)


def resolve_dist_impl(impl: Optional[str] = None) -> str:
    """Masked-distance dispatch: explicit ``impl`` > ``QUIP_DIST_IMPL`` env
    > the backend default (Pallas on TPU, ref on CPU)."""
    if impl is None:
        impl = env_choice("QUIP_DIST_IMPL", _HOST_IMPLS, "auto")
        impl = default_impl() if impl == "auto" else impl
    return _runs("dist", impl)


def resolve_join_impl(impl: Optional[str] = None) -> str:
    """Kernel-level join dispatch: explicit ``impl`` > ``QUIP_JOIN_IMPL``
    env > the backend default.  Distinct from the *engine-level*
    ``core.triggers.resolve_join_impl``, whose unset default is the NumPy
    oracle (``multi_match``) and never reaches this module; an explicit
    ``QUIP_JOIN_IMPL=ref|pallas`` routes the engine here, where the same
    knob then picks the kernel path."""
    if impl is None:
        impl = env_choice("QUIP_JOIN_IMPL", _HOST_IMPLS, "auto")
        impl = default_impl() if impl == "auto" else impl
    return _runs("join", impl)


def bloom_probe(
    bits: jnp.ndarray,
    folded: jnp.ndarray,
    *,
    num_hashes: int,
    log2m: int,
    impl: Optional[str] = None,
) -> np.ndarray:
    """``folded``: uint32 host-folded keys (see ``hashing.fold64``)."""
    impl = resolve_bloom_impl(impl)
    KERNEL_CALLS.record("bloom_probe", impl)
    if impl == "numpy":
        # host multiply-shift probe — same uint32 wraparound math as
        # hashing.hash_positions_np, but over pre-folded keys
        bits_np = np.asarray(bits, dtype=np.uint32)
        f = np.asarray(folded, dtype=np.uint32)[:, None]
        pos = ((f * MULTIPLIERS[None, :num_hashes]
                + OFFSETS[None, :num_hashes])
               >> np.uint32(32 - log2m)).astype(np.uint32)
        word = (pos >> np.uint32(5)).astype(np.int64)
        bit = pos & np.uint32(31)
        hit = (bits_np[word] >> bit) & np.uint32(1)
        return np.all(hit == 1, axis=1)
    f = np.asarray(folded, dtype=np.uint32)
    fp = _pad_rows(f, _bucket(len(f), 512))
    if impl == "pallas":
        out = bloom_probe_pallas(
            bits, fp, num_hashes=num_hashes, log2m=log2m, interpret=_interpret()
        )
    else:
        out = _probe_ref_jit(bits, fp, num_hashes, log2m)
    return np.asarray(out)[:len(f)]


_probe_ref_jit = jax.jit(_ref.bloom_probe_ref, static_argnums=(2, 3))


_hash_join_probe_sorted_jit = jax.jit(
    _ref.hash_join_probe_sorted_ref, static_argnums=(3,)
)


def hash_join_match(
    build_keys,
    probe_keys,
    *,
    impl: Optional[str] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """All (probe_idx, build_idx) pairs with equal int64 keys.

    The kernel-backed twin of ``core.triggers.multi_match`` (the NumPy
    oracle): pairs come back as host int64 arrays ordered by probe index,
    ascending build index within a probe — bit-identical to the oracle.

    Keys are folded to uint32 for the device (``hashing.fold64``); the
    kernels emit fold-level *candidates* (counts + fixed-size match blocks)
    which are verified here against the original 64-bit keys, so fold
    collisions never produce wrong pairs.  ``impl="numpy"`` sort-joins on
    the original int64 keys directly (no folding, no verification pass).
    """
    impl = resolve_join_impl(impl)
    b = np.ascontiguousarray(np.asarray(build_keys, dtype=np.int64))
    p = np.ascontiguousarray(np.asarray(probe_keys, dtype=np.int64))
    if len(b) == 0 or len(p) == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    KERNEL_CALLS.record("hash_join", impl)
    if impl == "numpy":
        return _hash_join_numpy(b, p)
    fb = fold64(b)
    fp = fold64(p)
    # static fold-level duplication bound (columns of the match block)
    max_dup = int(np.unique(fb, return_counts=True)[1].max())
    # bound the dense (chunk × max_dup) match block; chunking the probe side
    # keeps memory flat on skewed builds while preserving probe-major order
    # (a power of two, so only the last, ragged chunk is padded)
    chunk = max(256, 1 << (max(1, _DENSE_BUDGET // max_dup).bit_length() - 1))
    # build once (table / sorted order), probe per chunk
    if impl == "pallas":
        log2cap = table_log2cap(len(b))
        slot_key, slot_idx = hash_join_build_pallas(
            jnp.asarray(fb), log2cap=log2cap, interpret=_interpret()
        )
    else:
        order = np.argsort(fb, kind="stable").astype(np.int32)
        sorted_keys = jnp.asarray(fb[order])
        order = jnp.asarray(order)
    probe_parts, build_parts = [], []
    for lo in range(0, len(p), chunk):
        fpc = fp[lo:lo + chunk]
        # bucketing the ragged last chunk bounds the shapes jit compiles;
        # the padded probes' rows are sliced off before expansion
        fpp = jnp.asarray(_pad_rows(fpc, _bucket(len(fpc), 256)))
        if impl == "pallas":
            counts, matches = hash_join_probe_pallas(
                slot_key,
                slot_idx,
                fpp,
                log2cap=log2cap,
                max_dup=max_dup,
                interpret=_interpret(),
            )
        else:
            counts, matches = _hash_join_probe_sorted_jit(
                sorted_keys, order, fpp, max_dup
            )
        counts = np.asarray(counts, dtype=np.int64)[:len(fpc)]
        matches = np.asarray(matches)[:len(fpc)]
        # ragged expansion: row-major valid entries are already in oracle order
        probe_parts.append(
            np.repeat(np.arange(len(fpc), dtype=np.int64), counts) + lo
        )
        build_parts.append(matches[matches >= 0].astype(np.int64))
    probe_idx = np.concatenate(probe_parts)
    build_idx = np.concatenate(build_parts)
    # exact 64-bit verification kills fold-collision candidates
    keep = b[build_idx] == p[probe_idx]
    if not keep.all():
        probe_idx, build_idx = probe_idx[keep], build_idx[keep]
    return probe_idx, build_idx


_DENSE_BUDGET = 1 << 24  # match-block entries per probe chunk (64 MiB int32)


def _hash_join_numpy(b: np.ndarray, p: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Host sort-join on exact int64 keys: probe-major pairs, ascending
    build index within a probe (the stable argsort keeps equal keys in
    original order) — bit-identical to ``core.triggers.multi_match``."""
    order = np.argsort(b, kind="stable")
    sb = b[order]
    lo = np.searchsorted(sb, p, side="left")
    hi = np.searchsorted(sb, p, side="right")
    counts = hi - lo
    total = int(counts.sum())
    if total == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z
    probe_idx = np.repeat(np.arange(len(p), dtype=np.int64), counts)
    starts = np.repeat(lo, counts)
    offs = np.arange(total, dtype=np.int64) - np.repeat(
        np.cumsum(counts) - counts, counts
    )
    build_idx = order[starts + offs].astype(np.int64)
    return probe_idx, build_idx


def masked_distance(
    q: jnp.ndarray,
    qm: jnp.ndarray,
    r: jnp.ndarray,
    rm: jnp.ndarray,
    *,
    impl: Optional[str] = None,
) -> jnp.ndarray:
    impl = resolve_dist_impl(impl)
    KERNEL_CALLS.record("masked_distance", impl)
    if impl == "numpy":
        return _masked_distance_numpy(q, qm, r, rm)
    if impl == "pallas":
        return masked_distance_pallas(q, qm, r, rm, interpret=_interpret())
    return _dist_ref_jit(q, qm, r, rm)


def _masked_distance_numpy(q, qm, r, rm) -> np.ndarray:
    """float32 host port of ``ref.masked_distance_ref`` (same compute
    dtype, so the three impls agree to the kernel tests' tolerance)."""
    qm = np.asarray(qm, dtype=np.float32)
    rm = np.asarray(rm, dtype=np.float32)
    q = np.asarray(q, dtype=np.float32) * qm
    r = np.asarray(r, dtype=np.float32) * rm
    sq = (q * q) @ rm.T + qm @ (r * r).T - 2.0 * (q @ r.T)
    n_co = qm @ rm.T
    d = np.float32(q.shape[1])
    scaled = np.where(n_co > 0, sq * (d / np.maximum(n_co, np.float32(1.0))),
                      np.float32(np.inf))
    return np.maximum(scaled, np.float32(0.0))


_dist_ref_jit = jax.jit(_ref.masked_distance_ref)


def masked_knn(
    q: jnp.ndarray,
    qm: jnp.ndarray,
    r: jnp.ndarray,
    rm: jnp.ndarray,
    k: int,
    *,
    impl: Optional[str] = None,
    span=NULL_SPAN,
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k smallest masked distances per query row.  Query rows are
    padded to a power-of-two count; a padded row observes nothing, so its
    distances are +inf, and it is sliced off.

    ``span``, an open tracing span, is given ``nq_padded`` and adds to
    ``h2d_bytes`` the bytes of the host arrays the call hands to a device
    program (the distance program's inputs, or the host-computed distances
    that top-k reads).  Reference rows ``r``, ``rm`` already on the device
    are read in place and not counted."""
    nq = q.shape[0]
    rows = _bucket(nq, 128)
    qp = _pad_rows(np.asarray(q), rows)
    qmp = _pad_rows(np.asarray(qm), rows)
    dmat = masked_distance(qp, qmp, r, rm, impl=impl)
    if span is not NULL_SPAN:
        sent = (dmat,) if isinstance(dmat, np.ndarray) else (qp, qmp, r, rm)
        span.set(nq_padded=rows).add(
            h2d_bytes=sum(x.nbytes for x in sent
                          if isinstance(x, np.ndarray)))
    dist, idx = _top_k_jit(jnp.asarray(dmat), k)
    return np.asarray(dist)[:nq], np.asarray(idx)[:nq]


@functools.partial(jax.jit, static_argnums=(1,))
def _top_k_jit(dmat: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    neg, idx = jax.lax.top_k(-dmat, k)
    return -neg, idx


def resolve_knn_impl(impl: Optional[str] = None) -> str:
    """KNN-aggregation dispatch: explicit ``impl`` > ``QUIP_KNN_IMPL`` env >
    ``"numpy"`` (the vectorized host oracle, bit-identical to the seed
    per-row loop)."""
    if impl is None:
        impl = env_choice("QUIP_KNN_IMPL", _HOST_IMPLS, "numpy")
    return _runs("knn", impl)


def resolve_segment_impl(impl: Optional[str] = None) -> str:
    """Segment-reduction dispatch: explicit ``impl`` > ``QUIP_SEGMENT_IMPL``
    env > ``"numpy"`` (the per-segment host oracle, bit-identical to the
    interpreter's per-group reductions)."""
    if impl is None:
        impl = env_choice("QUIP_SEGMENT_IMPL", _HOST_IMPLS, "numpy")
    return _runs("segment", impl)


_SEGMENT_OPS = ("count", "sum", "min", "max")

_seg_ref_jit = jax.jit(_ref.segment_reduce_ref, static_argnums=(2, 3))


def _segment_numpy(vals: np.ndarray, seg: np.ndarray, num_segments: int,
                   op: str) -> np.ndarray:
    """Host oracle: per-segment ufunc reductions in row order.

    A stable argsort groups rows by segment while preserving row order
    within each segment, so each slice is the exact sequence the
    interpreter's boolean-mask extraction produces — float sums therefore
    use the same pairwise accumulation and are bit-identical to
    ``executor._aggregate``.
    """
    if np.issubdtype(vals.dtype, np.integer):
        out_dtype = np.int64
        lo, hi = np.iinfo(np.int64).min, np.iinfo(np.int64).max
    else:
        out_dtype = np.float64
        lo, hi = -np.inf, np.inf
    ident = {"sum": 0, "min": hi, "max": lo}[op]
    out = np.full(num_segments, ident, dtype=out_dtype)
    order = np.argsort(seg, kind="stable")
    sv = vals[order]
    bounds = np.searchsorted(seg[order], np.arange(num_segments + 1))
    for i in range(num_segments):
        sl = sv[bounds[i]:bounds[i + 1]]
        if len(sl) == 0:
            continue
        out[i] = sl.sum() if op == "sum" else (
            sl.min() if op == "min" else sl.max()
        )
    return out


def segment_reduce(
    values: Optional[np.ndarray],
    seg_ids: np.ndarray,
    num_segments: int,
    op: str,
    *,
    impl: Optional[str] = None,
) -> np.ndarray:
    """Grouped-aggregate segment reduction: (n,) values + (n,) segment ids
    in [0, num_segments) → (num_segments,) per-segment COUNT/SUM/MIN/MAX.

    ``values`` is ignored for ``op="count"`` (pass None).  Empty segments
    hold the reduction identity (count 0, sum 0, min/max dtype extreme) —
    callers mask them via the count op.

    ``impl`` (or ``QUIP_SEGMENT_IMPL``): ``numpy`` (default; float64 host
    reductions, bit-identical to the interpreter's per-group path and the
    impl the compiled executor uses), ``ref`` (jnp/XLA segment ops), or
    ``pallas`` (TPU kernel; interpret mode on the CPU).  The device paths
    compute in int32/float32, so integer results are identical while
    within int32 range and float results may differ in final-ulp
    accumulation order — they are benchmark/TPU paths, not the
    answer-serving default.
    """
    impl = resolve_segment_impl(impl)
    if op not in _SEGMENT_OPS:
        raise ValueError(f"unknown segment op {op!r}")
    seg = np.asarray(seg_ids, dtype=np.int64)
    num_segments = int(num_segments)
    if op == "count":
        vals = np.ones(len(seg), dtype=np.int64)
        op = "sum"  # count ≡ sum of ones, on every impl
    else:
        vals = np.asarray(values)
        if vals.shape != seg.shape:
            raise ValueError(
                f"values {vals.shape} and seg_ids {seg.shape} disagree"
            )
    if num_segments == 0:
        return np.zeros(0, dtype=np.int64 if op == "count"
                        else (np.int64 if np.issubdtype(vals.dtype, np.integer)
                              else np.float64))
    if impl == "numpy" or len(seg) == 0:
        KERNEL_CALLS.record("segment_reduce", "numpy")
        return _segment_numpy(vals, seg, num_segments, op)
    KERNEL_CALLS.record("segment_reduce", impl)
    integer = np.issubdtype(vals.dtype, np.integer)
    # pad rows (segment id -1: dropped) and segments to bucketed counts
    rows = _bucket(len(seg), 512)
    segs = _bucket(num_segments, 8)
    jv = jnp.asarray(_pad_rows(vals.astype(np.int32 if integer
                                           else np.float32), rows))
    js = jnp.asarray(_pad_rows(seg.astype(np.int32), rows, -1))
    if impl == "pallas":
        out = segment_reduce_pallas(
            jv, js, num_segments=segs, op=op, interpret=_interpret(),
        )
    else:
        out = _seg_ref_jit(jv, js, segs, op)
    res = np.asarray(out)[:num_segments].astype(
        np.int64 if integer else np.float64)
    if op in ("min", "max"):
        # the device paths computed in int32/float32, so empty segments hold
        # the *compute*-dtype extreme; restamp the output-dtype identity so
        # every impl honours the same empty-segment contract
        empty = np.bincount(seg[seg >= 0], minlength=num_segments) == 0
        if empty.any():
            if integer:
                info = np.iinfo(np.int64)
                res[empty] = info.max if op == "min" else info.min
            else:
                res[empty] = np.inf if op == "min" else -np.inf
    return res


def _mode_codes_numpy(codes: np.ndarray, num_classes: int) -> np.ndarray:
    """Per-row bincount argmax without a Python row loop: one flat bincount
    over ``row * num_classes + code`` (the ``np.apply_along_axis``-free
    trick), then a first-maximum argmax — ties to the smallest class."""
    b, k = codes.shape
    flat = np.arange(b, dtype=np.int64)[:, None] * num_classes + codes
    counts = np.bincount(flat.ravel(), minlength=b * num_classes)
    return counts.reshape(b, num_classes).argmax(axis=1)


_AGG_BUDGET = 1 << 24  # count/one-hot entries per mode chunk (memory bound)


_mean_ref_jit = jax.jit(_ref.neighbor_mean_ref)
_mode_ref_jit = jax.jit(_ref.neighbor_mode_ref, static_argnums=(1,))


def neighbor_aggregate(
    neigh: np.ndarray,
    *,
    categorical: bool,
    impl: Optional[str] = None,
) -> np.ndarray:
    """Aggregate a (b, k) neighbour-target matrix to (b,) imputed values.

    Float attributes take the per-row mean; dictionary-coded categorical
    attributes take the per-row mode with ties broken to the smallest
    value — the exact semantics of the seed imputer's per-row
    ``np.unique``/``argmax`` loop, now one vectorized pass.

    ``impl`` (or ``QUIP_KNN_IMPL``): ``numpy`` (default; float64 mean,
    bit-identical to the seed engine on CPU), ``ref`` (jnp/XLA, float32
    mean), or ``pallas`` (TPU kernel; interpret mode on the CPU).  The mode
    path dictionary-compresses on the host (``np.unique``) so the device
    kernels see dense class codes; integer results are identical across all
    three impls, float means may differ in final-ulp accumulation order.
    """
    impl = resolve_knn_impl(impl)
    neigh = np.asarray(neigh)
    if neigh.ndim != 2:
        raise ValueError(f"neighbor_aggregate expects (b, k), got {neigh.shape}")
    if neigh.shape[0] == 0:
        return np.zeros(0, dtype=np.float64)
    KERNEL_CALLS.record("neighbor_mode" if categorical else "neighbor_mean",
                        impl)
    if not categorical:
        if impl == "numpy":
            return neigh.astype(np.float64).mean(axis=1)
        b = neigh.shape[0]
        vals = jnp.asarray(_pad_rows(neigh.astype(np.float32),
                                     _bucket(b, 128)))
        if impl == "pallas":
            out = neighbor_mean_pallas(vals, interpret=_interpret())
        else:
            out = _mean_ref_jit(vals)
        return np.asarray(out, dtype=np.float64)[:b]
    uniq, inv = np.unique(neigh, return_inverse=True)
    codes = inv.reshape(neigh.shape).astype(np.int32)
    b, k = codes.shape
    num_classes = len(uniq)
    # row-chunk so the intermediate count matrix (numpy: b × classes;
    # ref/pallas: b × k × classes one-hot) stays within a fixed budget —
    # the reduction is per-row, so chunking is exact
    denom = num_classes if impl == "numpy" else num_classes * k
    chunk = max(1, _AGG_BUDGET // max(denom, 1))
    parts = []
    for lo in range(0, b, chunk):
        sub = codes[lo : lo + chunk]
        if impl == "numpy":
            parts.append(_mode_codes_numpy(sub, num_classes))
        elif impl == "pallas":
            # pad rows and classes to buckets: an extra class counts zero
            # and never wins, a padded row is sliced off
            out = neighbor_mode_pallas(
                jnp.asarray(_pad_rows(sub, _bucket(len(sub), 128))),
                num_classes=_bucket(num_classes, 256),
                interpret=_interpret(),
            )
            parts.append(np.asarray(out)[:len(sub)])
        else:
            parts.append(np.asarray(_mode_ref_jit(jnp.asarray(sub),
                                                  num_classes)))
    idx = parts[0] if len(parts) == 1 else np.concatenate(parts)
    return uniq[idx].astype(np.float64)
