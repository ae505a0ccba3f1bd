"""XGBoost-hist training and prediction as device programs.

One program trains every tree of one (table, attribute) model at one
training-row bucket (:func:`boost`), and one program predicts a batch of
rows from every tree (:func:`predict`).  No host round trip happens per
round or per level.

Layout.  ``bins`` is ``(n, F)`` int16: each training row's bin index per
feature, ``0 .. MAX_BIN - 1`` for an observed value and ``MISSING`` for a
missing one.  A tree of depth ``D`` is stored level by level as a heap: the
node ``k`` of level ``d`` sits at ``2**d - 1 + k``; its children are
``2k`` and ``2k + 1`` of level ``d + 1``.  Per round the program returns

* ``feat``  ``(2**D - 1,)`` int32: the split feature, -1 where the node does
  not split (its rows all go to child ``2k``, which does not split either);
* ``thr``   ``(2**D - 1,)`` int32: rows with ``bin <= thr`` go left;
* ``dleft`` ``(2**D - 1,)`` bool: whether missing rows go left (the default
  direction of Algorithm 3);
* ``leaves`` ``(n,)`` int8: the leaf (``0 .. 2**D - 1``) each training row
  ends in.

The float32 leaf weights steer the program's own boosting; the model's
weights are refit from ``leaves`` in float64 on the host
(``repro.imputers.xgb_hist``), and :func:`predict` returns each row's leaf
per tree, which the host sums in float64.

Algorithm (Chen and Guestrin, KDD 2016: the gain of eq. 7, the
sparsity-aware split finding of Algorithm 3), squared error so ``h = 1``:

1. per level, histograms of ``(G, H)`` per (node, feature, bin) plus the
   missing slot: the sums of the rows' ``g`` and ``h``;
2. per (feature, bin ``b``, direction), the left side is the bins ``<= b``
   plus the missing slot if missing rows go left, the right side the
   node's total less the left; a candidate is valid where ``HL`` and
   ``HR`` are both at least ``min_child_weight``.  Eq. 7's
   ``GL**2/(HL+l) + GR**2/(HR+l) - G**2/(H+l)`` is, with ``a = HL + l``
   and ``b = HR + l``, ``Q - l * G**2 / ((H + l) * (H + 2l))`` where
   ``Q = (GL * b - GR * a)**2 / (a * b * (a + b))``: the second term is
   the node's, so candidates rank by ``Q``, free of the cancellation of
   eq. 7's large terms;
3. the node takes the highest ``Q``, ties to the lowest feature, then the
   lowest bin, then missing rows to the left (the first maximum of the
   flattened ``(feature, bin, direction)`` order, left first); it splits
   if its gain less ``gamma`` is positive;
4. the chosen bin moves down to the lowest bin with the same rows on the
   left in this node, and the direction to the left where the node has no
   missing row: candidates that part the node's rows alike are one choice;
5. after ``D`` levels each leaf takes ``-eta * G / (H + lambda)`` and every
   row's ``g`` moves by its leaf's weight.

The bin one-hot.  Before its first round :func:`boost` builds the rows'
bin one-hot once from ``bins`` (:func:`_onehot`): ``(n, F * SLOTS)`` int8,
one byte per (row, feature, slot), 135 MB at ``n = 65536`` and ``F = 8``,
269 MB at ``MAX_TRAIN_ROWS``.  It stays in device memory for the fit, and
each level's chunk loop multiplies a ``CHUNK``-row slice of it; no level
compares or relays out the bins again.

Precision.  ``g`` is carried as two float32s (``g_hi + g_lo``, updated by
an error-free sum).  For the histograms it enters as ``LIMBS`` balanced
base-256 digits of ``g / q`` (``q`` a power of two, ``digit_unit``), each an
integer of at most 128 in magnitude and exact in bfloat16; the one-hots are
exact, so every product in the bfloat16 matrix products is exact and every
float32 sum is an integer below ``2**24``: the accumulation is exact in any
order, on any backend.  ``G`` is read back from its digit sums rounded
once to float32 (``_value``); ``H`` is a count.  So a histogram sum is the
float32 rounding of the exact sum of the rows' ``g``, each to within ``q``,
and the rest of the split search is float32 arithmetic on those sums.
Padded rows carry ``g = h = 0`` and change no sum.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["MAX_BIN", "MISSING", "SLOTS", "boost", "predict", "digit_unit",
           "train_bucket", "predict_bucket", "filled_chunks", "PREDICT_BATCH",
           "MAX_TRAIN_ROWS", "CHUNK"]

MAX_BIN = 256
#: the missing slot of every feature
MISSING = MAX_BIN
SLOTS = MAX_BIN + 1
#: most query rows one prediction program takes
PREDICT_BATCH = 16384
#: base-256 digits of a gradient in the histogram products
LIMBS = 5
#: training rows a histogram product takes at a time
CHUNK = 4096
#: most training rows a boosting program takes: a digit sum over them
#: stays an integer below ``2**24``, exact in float32
MAX_TRAIN_ROWS = 1 << 17

_BF16 = jnp.bfloat16
_F32 = jnp.float32


def train_bucket(n: int) -> int:
    """Training rows padded to a power of two from ``4 * CHUNK``: the
    histograms run over the chunks that hold rows, so the padding costs
    only the per-row routing, and four programs cover 1 to 131,072 rows."""
    size = 4 * CHUNK
    while size < n:
        size *= 2
    return size


def filled_chunks(n: int) -> int:
    """The chunks of ``CHUNK`` rows that ``n`` training rows fill."""
    return -(-n // CHUNK)


def predict_bucket(n: int) -> int:
    """Query rows padded to a power of two from 128 (at most
    ``PREDICT_BATCH`` a program)."""
    size = 128
    while size < n:
        size *= 2
    return size


def _pick(table, idx):
    """``table[..., idx]`` along the last axis, for a small table: a
    one-hot select and sum, where a gather would run element by element on
    the TPU.  ``table`` broadcasts against ``idx[..., None]``."""
    hit = idx[..., None] == jnp.arange(table.shape[-1], dtype=idx.dtype)
    if table.dtype == jnp.bool_:
        return jnp.any(hit & table, axis=-1)
    return jnp.sum(jnp.where(hit, table, jnp.zeros((), table.dtype)),
                   axis=-1)


def _two_sum(a, b):
    """``(s, e)`` with ``s = fl(a + b)`` and ``a + b = s + e`` exactly."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _digits(hi, lo, inv_q):
    """``(n, LIMBS)`` bfloat16: balanced base-256 digits of ``(hi + lo) /
    q`` to the nearest integer (within one), least significant first, each
    in -128..128 (exact in bfloat16).  ``inv_q`` is a power of two: every
    step is exact but the one that adds ``lo``, which rounds below one."""
    x = hi * inv_q
    out = []
    for e in (32, 24):
        d = jnp.round(x * 2.0 ** -e)
        x = x - d * 2.0 ** e
        out.append(d)
    x = x + lo * inv_q
    for e in (16, 8):
        d = jnp.round(x * 2.0 ** -e)
        x = x - d * 2.0 ** e
        out.append(d)
    out.append(jnp.round(x))
    return jnp.stack(out[::-1], axis=-1).astype(_BF16)


def _value(sums, q):
    """The digit sums ``(..., LIMBS)`` (float32 integers) as float32: the
    exact value ``q * sum_j 256**j * S_j`` rounded once (the terms are
    exact, and their sum is carried in two floats)."""
    hi = sums[..., LIMBS - 1] * (q * 2.0 ** (8 * (LIMBS - 1)))
    lo = jnp.zeros_like(hi)
    for j in range(LIMBS - 2, -1, -1):
        hi, e = _two_sum(hi, sums[..., j] * (q * 2.0 ** (8 * j)))
        lo = lo + e
    return hi + lo


def _onehot(bins):
    """``(n, F * SLOTS)`` int8: ``1`` at column ``f * SLOTS + bins[:, f]``
    of every row and feature, ``0`` elsewhere.  The compare runs in this
    flat layout, each column against its feature's bin (one select per
    feature), so XLA writes the bytes in one pass; a ``(n, F, SLOTS)``
    compare reshaped to it, or a gather of each column's bin, first writes
    an int32 ``(n, F * SLOTS)`` array or a relaid copy."""
    nf = bins.shape[1]
    col = jnp.arange(nf * SLOTS, dtype=bins.dtype)
    feat, slot = col // SLOTS, col % SLOTS
    val = bins[:, :1]
    for f in range(1, nf):
        val = jnp.where(feat == f, bins[:, f:f + 1], val)
    return (val == slot).astype(jnp.int8)


def _node_sums(w, node, nodes: int, chunks, onehot=None):
    """``(nodes, K, C)`` float32: per node, the sums of ``w``'s ``K``
    columns over the rows in each of ``C`` slots -- the columns of
    ``onehot`` (``_onehot``: each feature's bins) or, without it, one slot
    for every row.  The rows are taken ``CHUNK`` at a time, the first
    ``chunks`` chunks only.  Every product is exact and every sum an
    integer below ``2**24``, so the float32 accumulation is exact in any
    order."""
    k = w.shape[1]
    cols = 1 if onehot is None else onehot.shape[1]
    ids = jnp.arange(nodes, dtype=node.dtype)

    def add(c, acc):
        lo = c * CHUNK
        nd = jax.lax.dynamic_slice_in_dim(node, lo, CHUNK)
        wc = jax.lax.dynamic_slice_in_dim(w, lo, CHUNK)
        lhs = ((nd[:, None] == ids).astype(_BF16)[:, :, None]
               * wc[:, None, :]).reshape(CHUNK, nodes * k)
        if onehot is None:
            rhs = jnp.ones((CHUNK, 1), _BF16)
        else:
            rhs = jax.lax.dynamic_slice_in_dim(onehot, lo, CHUNK).astype(
                _BF16)
        return acc + jax.lax.dot_general(
            lhs, rhs, (((0,), (0,)), ((), ())), preferred_element_type=_F32)

    out = jax.lax.fori_loop(0, chunks, add,
                            jnp.zeros((nodes * k, cols), _F32))
    return out.reshape(nodes, k, cols)


def _level(bins, onehot, chunks, w, node, frozen, q, *, lam, gamma, mcw):
    """Histograms, split finding and routing for one level of ``nodes``
    nodes.  Returns the level's ``(feat, thr, dleft)`` and the rows' nodes
    and frozen flags on the next level."""
    nodes = frozen.shape[0]
    n, nf = bins.shape
    sums = _node_sums(w, node, nodes, chunks, onehot).reshape(
        nodes, LIMBS + 1, nf, SLOTS)
    sums = jnp.moveaxis(sums, 1, -1)  # (nodes, F, slots, digits + count)
    total = jnp.sum(sums[:, 0], axis=1)  # (nodes, digits + count)
    # a scan in log steps (a cumsum lowers to a reduce-window on the TPU,
    # a window per bin); the sums are integers, exact in any order
    pre = jax.lax.associative_scan(jnp.add, sums[:, :, :MAX_BIN], axis=2)
    miss = sums[:, :, MISSING:]
    left = jnp.stack([pre + miss, pre], axis=3)  # (nodes, F, bins, dir, .)
    right = total[:, None, None, None] - left
    gl, gr = _value(left[..., :LIMBS], q), _value(right[..., :LIMBS], q)
    hl, hr = left[..., LIMBS], right[..., LIMBS]
    a, b = hl + lam, hr + lam
    # the side with fewer rows (then the lesser G) first: a candidate and
    # its mirror (the same rows, sides swapped) get the same bits of Q
    # however the backend contracts products and sums
    swap = (hl > hr) | ((hl == hr) & (gl > gr))
    delta = (jnp.where(swap, gr, gl) * jnp.where(swap, a, b)
             - jnp.where(swap, gl, gr) * jnp.where(swap, b, a))
    score = jnp.where((hl >= mcw) & (hr >= mcw),
                      delta * delta / (a * b * (a + b)), -jnp.inf)
    score = score.reshape(nodes, -1)
    best = jnp.argmax(score, axis=-1)
    top = jnp.max(score, axis=-1)
    gt, ht = _value(total[:, :LIMBS], q), total[:, LIMBS]
    gain = 0.5 * (top - lam * gt * gt / ((ht + lam) * (ht + 2 * lam))) - gamma
    split = ~frozen & (gain > 0)
    f = (best // (2 * MAX_BIN)).astype(jnp.int32)
    bi = ((best // 2) % MAX_BIN).astype(jnp.int32)
    left_first = best % 2 == 0
    # step 4: the lowest bin with the same rows on the left, and missing
    # rows to the left where the node has none
    counts = jnp.take_along_axis(pre[..., LIMBS], f[:, None, None],
                                 axis=1)[:, 0]
    at = jnp.take_along_axis(counts, bi[:, None], axis=1)
    thr = jnp.sum(counts < at, axis=-1).astype(jnp.int32)
    no_missing = jnp.take_along_axis(miss[:, :, 0, LIMBS], f[:, None],
                                     axis=1)[:, 0] == 0
    dleft = left_first | no_missing
    xb = _pick(bins, _pick(f, node))
    go_left = jnp.where(xb == MISSING, _pick(dleft, node),
                        xb <= _pick(thr, node))
    go_left = go_left | ~_pick(split, node)
    child = 2 * node + jnp.where(go_left, 0, 1).astype(node.dtype)
    feat = jnp.where(split, f, -1)
    return feat, thr, dleft, child, jnp.repeat(~split | frozen, 2)


@functools.partial(jax.jit, static_argnames=(
    "rounds", "depth", "eta", "lam", "gamma", "min_child_weight"))
def boost(bins, g_hi, g_lo, h, q, chunks, *, rounds: int, depth: int,
          eta: float, lam: float, gamma: float, min_child_weight: float):
    """Train ``rounds`` trees.  ``bins`` ``(n, F)`` int16; ``g_hi + g_lo``
    ``(n,)`` float32 each, the base score less the target (0 on padded
    rows); ``h`` ``(n,)`` float32, 1 on a training row and 0 on a padded
    one; ``q`` the unit of ``g``'s digits, a power of two with ``|g| / q``
    below ``2**39`` (``digit_unit``); the training rows lie in the first
    ``chunks`` chunks of ``CHUNK`` rows.  Returns ``(feat, thr, dleft,
    leaves)`` stacked over rounds."""
    bins = bins.astype(jnp.int32)
    n, nf = bins.shape
    chunks = jnp.asarray(chunks, jnp.int32)
    leaves = 1 << depth
    q = q.astype(_F32)
    inv_q = 1.0 / q
    hb = h.astype(_BF16)[:, None]
    onehot = _onehot(bins)

    def one_round(carry, _):
        g_hi, g_lo = carry
        w = jnp.concatenate([_digits(g_hi, g_lo, inv_q), hb], axis=-1)
        node = jnp.zeros(n, jnp.int32)
        frozen = jnp.zeros(1, bool)
        feats, thrs, dlefts = [], [], []
        for _d in range(depth):
            feat, thr, dleft, node, frozen = _level(
                bins, onehot, chunks, w, node, frozen, q, lam=lam,
                gamma=gamma, mcw=min_child_weight)
            feats.append(feat)
            thrs.append(thr)
            dlefts.append(dleft)
        sums = _node_sums(w, node, leaves, chunks)[..., 0]
        gl, hl = _value(sums[:, :LIMBS], q), sums[:, LIMBS]
        leaf = jnp.where(hl > 0, -eta * gl / (hl + lam), 0.0).astype(_F32)
        g_hi, e = _two_sum(g_hi, _pick(leaf, node) * h)
        return (g_hi, g_lo + e), (
            jnp.concatenate(feats), jnp.concatenate(thrs),
            jnp.concatenate(dlefts), node.astype(jnp.int8))

    _g, trees = jax.lax.scan(one_round, (g_hi.astype(_F32),
                                         g_lo.astype(_F32)),
                             None, length=rounds)
    return trees


def digit_unit(g) -> float:
    """The unit ``q`` of ``boost``'s digits for gradients ``g`` (host
    float64): a power of two with ``4 * max |g| / q`` at most ``2**39``.
    Boosting never moves a residual past the target's range, at most twice
    the largest ``|g|`` at the start."""
    top = float(abs(g).max()) if len(g) else 0.0
    if top == 0.0:
        return 1.0
    return 2.0 ** (math.ceil(math.log2(top)) + 2 - 39)


@jax.jit
def predict(feat, thr, dleft, bins):
    """``(rounds, m)`` int8: the leaf each row reaches in each tree.
    ``bins`` ``(m, F)`` int16; the trees as :func:`boost` returns them."""
    bins = bins.astype(jnp.int32)
    rounds = feat.shape[0]
    depth = (feat.shape[1] + 1).bit_length() - 1
    m, nf = bins.shape
    node = jnp.zeros((rounds, m), jnp.int32)
    for d in range(depth):
        level = slice((1 << d) - 1, (2 << d) - 1)
        f = _pick(feat[:, None, level], node)
        t = _pick(thr[:, None, level], node)
        dl = _pick(dleft[:, None, level], node)
        xb = _pick(bins[None], f)
        go_left = (f < 0) | jnp.where(xb == MISSING, dl, xb <= t)
        node = 2 * node + jnp.where(go_left, 0, 1)
    return node.astype(jnp.int8)
