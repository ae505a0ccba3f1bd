"""The plain reference of the XGBoost-hist imputer kind
(``imputers/xgb_hist.py``): XGBoost-hist written out in float64 numpy, with
the ties float32 cannot settle followed as branches.  It imports nothing of
the program.  ``fit_attribute`` is the whole of one attribute's imputation,
a plain function so that a process pool can run attributes side by side.
It imputes every missing cell of an attribute with the semantics
the configuration states (Chen and Guestrin, KDD 2016, eq. 7 and
Algorithm 3; ``tree_method=hist``, squared error, so ``h = 1``), written
out in float64 numpy:

* training rows observe the attribute; features are the table's other
  columns but ``id``, missing cells left missing;
* cuts per feature from its observed values on the training rows: every
  distinct value but the least where there are at most 256, else the
  distinct values among the sorted values at positions ``j * m // 256``,
  ``j = 1 .. 255``; a value's bin is the number of cuts at or below it, a
  missing cell the missing slot;
* base score the target's mean, ``g = base - y``; per round and level,
  histograms of ``(G, H)`` per (node, feature, bin) and the missing slot;
  per (feature, bin, direction) the left side is the bins at or below it,
  plus the missing slot when missing rows go left, the right side the
  node's total less the left; candidates rank by eq. 7's gain less the
  node's own term, ``Q = (GL*b - GR*a)**2 / (a*b*(a+b))`` with ``a = HL +
  lambda`` and ``b = HR + lambda``, valid where both sides hold
  ``min_child_weight``; the highest wins, ties to the lowest feature, bin,
  then missing rows left; the bin moves down to the lowest one with the
  same rows on the left and the direction to the left where the node has
  no missing row; the node splits if eq. 7's gain less ``gamma`` is
  positive, else it and its subtree pass every row to the left child;
* after 6 levels each leaf takes ``-eta * G / (H + lambda)`` over its rows
  and every row's ``g`` moves by it; after 100 rounds a missing cell is the
  base plus its leaves' weights, added tree by tree (integer attributes
  snap to the nearest observed value).

**What the program can differ in.**  The program grows its trees from
histogram sums exact to float32 rounding (each row's ``g`` to within the
digit unit ``q``, ``digit_unit``) with float32 scores, and then refits the
leaf weights and sums predictions in float64 as this reference computes
them (``XgbHistImputer``).  So where it makes the reference's split choices
its values are the reference's to the last bit, and a cell is compared to
float64 rounding.  Candidates that part a node's training rows alike, or as
mirrors, have bit-equal scores in the program (equal exact sums, the side
with fewer rows first), so it takes the first of them in its flattened
order, and so does the reference.  Any other candidate within the band of
the best (``band``: ``UNITS`` roundoffs of the score, plus what the digits'
rounding and ``delta``'s float32 roundings move it by) may win in float32,
and so may the split test within its band of 0: a *tie*.  ``UNITS = 64`` is
set from measurement, not from a bound: with exact sums the program's
error in the score difference between a node's best and its runners-up
peaked at 12 roundoffs over 100 rounds of ``exams.weight`` at 40% size
(PERF.md, section 2); it covers the float32 rounding of the sums and of
the scores, and the drift of the program's float32 leaf weights.  ``H``
counts rows, exact in float32: the ``min_child_weight`` test is never
close.

A tie is followed as a branch from its round, in (round, level, node)
order, up to ``MAX_BRANCHES`` an attribute, each branch following only the
ties after its own; a tie after which any two trajectories can part by at
most ``BOUND_SHARE`` of the target's range (``Imputation._after``) widens
the cells by that bound instead.  A cell is *ambiguous* where the
trajectories' values differ, and admits their interval; past
``MAX_BRANCHES`` the attribute's cells are *open*: any value in its
observed range.  Every other cell is *determined*.

The control is this reference with every histogram sum (and each leaf's
``G`` and ``H``) rounded to bfloat16; it must come out as not correct.
"""

from __future__ import annotations

import math

import numpy as np

__all__ = ["MAX_BIN", "MISSING", "UNITS", "MAX_BRANCHES", "BOUND_SHARE",
           "cuts", "bins_of", "band", "digit_unit", "fit_attribute"]

MAX_BIN = 256
MISSING = MAX_BIN
SLOTS = MAX_BIN + 1
#: float32 unit roundoff
EPS32 = 2.0 ** -24
#: roundoffs of a candidate's score its float32 value may be off by
UNITS = 64.0
#: trajectories an attribute may branch into beyond the reference's own
MAX_BRANCHES = 8
#: a tie whose trajectories can part by at most this share of the target's
#: range (``after``) widens the cells by that bound instead of being
#: followed
BOUND_SHARE = 1e-4


def cuts(values: np.ndarray) -> np.ndarray:
    s = np.sort(np.asarray(values, dtype=np.float64))
    u = np.unique(s)
    if len(u) <= MAX_BIN:
        return u[1:]
    return np.unique(s[np.arange(1, MAX_BIN) * len(s) // MAX_BIN])


def bins_of(x, observed, cut):
    return np.where(observed, np.searchsorted(cut, x, side="right"), MISSING)


def _bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 (to nearest even), as float64."""
    b = np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


def band(score, delta, a, b, hl, hr, q, gl, gr):
    """The band of candidates' scores ``Q = delta**2 / (a * b * (a + b))``
    (``delta = GL * b - GR * a``, ``a = HL + lambda``, ``b = HR +
    lambda``): ``UNITS`` roundoffs of ``Q``, and what ``delta`` off by
    ``dd`` moves it by: ``GL`` and ``GR`` off by ``q`` a row (the digits'
    rounding), and ``delta``'s float32 roundings (``GL`` and ``GR`` rounded
    once, two products and a difference: four roundoffs of its terms,
    which cancel where the split is weak)."""
    dd = (q * (b * hl + a * hr)
          + 4 * EPS32 * (np.abs(gl) * b + np.abs(gr) * a))
    return (UNITS * EPS32 * np.abs(score)
            + (2 * np.abs(delta) * dd + dd * dd) / (a * b * (a + b)))


def digit_unit(g: np.ndarray) -> float:
    """The unit of the program's digits of ``g``: the power of two
    ``2**(ceil(log2(max |g|)) + 2 - 39)`` (1 where every ``g`` is 0)."""
    top = float(np.abs(g).max()) if len(g) else 0.0
    return 1.0 if top == 0.0 else 2.0 ** (math.ceil(math.log2(top)) - 37)


class _Trajectory:
    """One sequence of trees: per round ``feat``, ``thr``, ``dleft`` (heap
    order) and the float64 leaf weights, and the ties met that part the
    training rows otherwise (``branches``: ``(t, d, k, choice, g)``, with
    ``g`` at the round's start)."""

    def __init__(self, rounds: int, depth: int):
        inner = (1 << depth) - 1
        self.feat = np.full((rounds, inner), -1, np.int64)
        self.thr = np.zeros((rounds, inner), np.int64)
        self.dleft = np.ones((rounds, inner), bool)
        self.weights = np.zeros((rounds, 1 << depth))
        self.branches: list = []


class _Fit:
    """The reference's boosting over one attribute's training rows."""

    def __init__(self, bins: np.ndarray, y: np.ndarray, p: dict,
                 control: bool):
        self.bins = bins
        self.p = p
        self.control = control
        n, nf = bins.shape
        self.key0 = (np.arange(nf) * SLOTS)[None, :] + bins
        self.base = float(y.mean())
        self.g0 = self.base - y
        self.q = digit_unit(self.g0)

    def _hist(self, key, N, w=None):
        """Per (node, feature, slot) sums of ``w`` (``np.repeat``-ed over
        the features; counts where ``None``) over the rows' ``key``s."""
        nf = self.bins.shape[1]
        out = np.bincount(key, weights=w, minlength=N * nf * SLOTS)
        return out.reshape(N, nf, SLOTS).astype(np.float64)

    def run(self, traj: _Trajectory, g=None, start: int = 0, force=None):
        """Rounds ``start ..`` into ``traj``, from ``g`` at the start of
        round ``start``.  ``force``: ``(d, k, choice)`` at round ``start``:
        ``choice`` a rule ``(f, thr, dleft)``, or ``None`` for no split."""
        p = self.p
        if g is None:
            g = self.g0
        n, nf = self.bins.shape
        lam, depth = p["lam"], p["depth"]
        rnd = _bf16 if self.control else (lambda x: x)
        for t in range(start, p["rounds"]):
            node = np.zeros(n, np.int64)
            frozen = np.zeros(1, bool)
            at_force = force if t == start else None
            gf = np.repeat(g, nf)
            for d in range(depth):
                N = 1 << d
                key = (node[:, None] * (nf * SLOTS) + self.key0).ravel()
                G = rnd(self._hist(key, N, gf))
                H = rnd(self._hist(key, N))
                gt, ht = G[:, 0].sum(-1), H[:, 0].sum(-1)
                pg = np.cumsum(G[..., :MAX_BIN], -1)
                ph = np.cumsum(H[..., :MAX_BIN], -1)
                GL = np.stack([pg + G[..., MISSING:], pg], -1)
                HL = np.stack([ph + H[..., MISSING:], ph], -1)
                GR = gt[:, None, None, None] - GL
                HR = ht[:, None, None, None] - HL
                a, b = HL + lam, HR + lam
                valid = (HL >= p["min_child_weight"]) & (
                    HR >= p["min_child_weight"])
                delta = GL * b - GR * a
                with np.errstate(divide="ignore", invalid="ignore"):
                    score = np.where(valid, delta * delta
                                     / (a * b * (a + b)), -np.inf)
                flat = score.reshape(N, -1)
                best = flat.argmax(1)
                top = flat[np.arange(N), best]
                # eq. 7's node term: G^2/(HL+HR+2l) - G^2/(H+l)
                node_term = lam * gt * gt / ((ht + lam) * (ht + 2 * lam))
                gain = 0.5 * (top - node_term) - p["gamma"]
                split = ~frozen & (gain > 0)
                f = best // (2 * MAX_BIN)
                bi = (best // 2) % MAX_BIN
                # step 4: the lowest bin with the same rows on the left
                change = np.ones(ph.shape, bool)
                change[..., 1:] = ph[..., 1:] != ph[..., :-1]
                low = np.maximum.accumulate(
                    np.where(change, np.arange(MAX_BIN), 0), axis=-1)
                no_miss = H[..., MISSING] == 0
                thr = low[np.arange(N), f, bi]
                dleft = (best % 2 == 0) | no_miss[np.arange(N), f]
                if not self.control:
                    bnd = np.where(valid, band(score, delta, a, b, HL, HR,
                                               self.q, GL, GR),
                                   0.0).reshape(N, -1)
                    dn = self.q * ht
                    bnode = (EPS32 * UNITS * node_term + lam * (
                        2 * np.abs(gt) * dn + dn * dn)
                        / ((ht + lam) * (ht + 2 * lam)))
                    self._ties(traj, t, d, node, g, flat, bnd, best, top,
                               bnode, gain, split, frozen, f, thr, dleft,
                               low, no_miss, at_force)
                if at_force and d == at_force[0]:
                    k, choice = at_force[1], at_force[2]
                    split[k] = choice is not None
                    if choice is not None:
                        f[k], thr[k], dleft[k] = choice
                heap = N - 1 + np.arange(N)
                traj.feat[t, heap] = np.where(split, f, -1)
                traj.thr[t, heap] = thr
                traj.dleft[t, heap] = dleft
                xb = self.bins[np.arange(n), f[node]]
                go = np.where(xb == MISSING, dleft[node], xb <= thr[node])
                node = 2 * node + ~(go | ~split[node])
                frozen = np.repeat(~split | frozen, 2)
            leaves = 1 << depth
            Gl = rnd(np.bincount(node, weights=g, minlength=leaves))
            Hl = rnd(np.bincount(node, minlength=leaves).astype(np.float64))
            w = np.where(Hl > 0, -p["eta"] * Gl / (Hl + lam), 0.0)
            traj.weights[t] = w
            g = g + w[node]

    def _ties(self, traj, t, d, node, g, flat, bnd, best, top, bnode,
              gain, split, frozen, f, thr, dleft, low, no_miss, skip):
        """Settle each node's rule among the candidates within the band of
        its best, and record the ties (module docstring).  Candidates that
        part the node's training rows as the best does, or as its mirror,
        have bit-equal float32 scores in the program (their sums are
        exact), so the program takes the first of them in its flattened
        order: so does the reference.  Any other candidate in the band is a
        tie.  ``skip``: a branch's forced ``(d, k, choice)``; the ties of
        its round up to and at that node are its parent's."""
        N = len(top)
        btop = bnd[np.arange(N), best]
        bgain = 0.5 * (btop + bnode) + EPS32 * np.abs(gain)
        order = None
        for k in range(N):
            record = skip is None or (d, k) > tuple(skip[:2])
            if frozen[k] or not np.isfinite(top[k]):
                continue
            if record and abs(gain[k]) <= bgain[k]:  # split test tied
                traj.branches.append(
                    (t, d, k, None if split[k] else
                     (int(f[k]), int(thr[k]), bool(dleft[k])), g))
            near = np.nonzero(flat[k] + bnd[k] >= top[k] - btop[k])[0]
            if len(near) <= 1:
                continue
            cf = near // (2 * MAX_BIN)
            rules = {}  # normalized rule -> its first candidate
            for c, rule in zip(near, zip(
                    cf.tolist(), low[k][cf, (near // 2) % MAX_BIN].tolist(),
                    ((near % 2 == 0) | no_miss[k][cf]).tolist())):
                rules.setdefault(rule, c)
            if len(rules) == 1:
                continue
            if order is None:
                order = np.argsort(node, kind="stable")
                starts = np.searchsorted(node[order], np.arange(N + 1))
            rows = order[starts[k]:starts[k + 1]]
            # candidates by the partition of the rows they make, mirrors
            # alike, each partition's first candidate
            first = {}
            for rule, c in rules.items():
                left = self._left(rows, rule)
                key = (left if not left[0] else ~left).tobytes()
                if key not in first or c < first[key][0]:
                    first[key] = (c, rule)
            own = self._left(rows, (int(f[k]), int(thr[k]), bool(dleft[k])))
            own = (own if not own[0] else ~own).tobytes()
            f[k], thr[k], dleft[k] = first.pop(own)[1]
            if record and split[k]:
                traj.branches.extend((t, d, k, rule, g)
                                     for _c, rule in sorted(first.values()))

    def _left(self, rows, rule):
        f, thr, dleft = rule
        xb = self.bins[rows, f]
        return np.where(xb == MISSING, dleft, xb <= thr)


def predict(traj: _Trajectory, bins: np.ndarray, base: float, depth: int):
    """The base plus each row's leaf weights, added tree by tree."""
    rows = np.arange(len(bins))
    val = np.full(len(bins), base)
    for t, w in enumerate(traj.weights):
        node = np.zeros(len(bins), np.int64)
        for d in range(depth):
            heap = (1 << d) - 1 + node
            f = traj.feat[t, heap]
            xb = bins[rows, np.maximum(f, 0)]
            go = (f < 0) | np.where(xb == MISSING, traj.dleft[t, heap],
                                    xb <= traj.thr[t, heap])
            node = 2 * node + ~go
        val += w[node]
    return val


def after(p: dict, t: int, g: np.ndarray) -> float:
    """How far two trajectories that share ``g`` at the start of round
    ``t`` can part in any prediction by the last round: a leaf weight is at
    most ``eta * max |g|``, and so a round grows ``max |g|`` by at most
    ``1 + eta``; the two sums of the rounds' weights differ by at most
    ``2 * max |g| * ((1 + eta)**(rounds - t) - 1)``."""
    top = float(np.abs(g).max()) if len(g) else 0.0
    return 2.0 * top * ((1.0 + p["eta"]) ** (p["rounds"] - t) - 1.0)


def fit_attribute(btr: np.ndarray, y: np.ndarray, bq: np.ndarray, p: dict,
                  control: bool):
    """One attribute's imputation: the training rows' bins ``btr`` and
    targets ``y``, the missing rows' bins ``bq``.  Returns ``(val, lo, hi,
    opened)``: the reference's value of each missing row, the least and
    largest value any followed trajectory gives it (widened by the bounds
    of the ties left unfollowed), and whether more ties were met than
    ``MAX_BRANCHES`` (then every row admits ``y``'s range).  ``p``:
    ``rounds``, ``depth``, ``eta``, ``lam``, ``gamma``,
    ``min_child_weight``."""
    fit = _Fit(btr, y, p, control)
    depth = p["depth"]
    main = _Trajectory(p["rounds"], depth)
    fit.run(main)
    # a trajectory and its bound: every tie it leaves unfollowed moves its
    # values by at most ``after(t)``
    trajs = [[main, 0.0]]
    pending = [(trajs[0], b) for b in main.branches]
    limit = BOUND_SHARE * float(np.ptp(y)) if len(y) else 0.0
    while pending:
        src, (t, d, k, choice, g) = pending.pop(0)
        bound = after(p, t, g)
        if bound <= limit:
            src[1] = max(src[1], bound)
            continue
        if len(trajs) > MAX_BRANCHES:
            break
        br = _Trajectory(p["rounds"], depth)
        for name in ("feat", "thr", "dleft", "weights"):
            getattr(br, name)[:t] = getattr(src[0], name)[:t]
        fit.run(br, g, start=t, force=(d, k, choice))
        trajs.append([br, 0.0])
        pending.extend((trajs[-1], b) for b in br.branches)
    val = predict(main, bq, fit.base, depth)
    lo, hi = val - trajs[0][1], val + trajs[0][1]
    for br, bound in trajs[1:]:
        v = predict(br, bq, fit.base, depth)
        lo, hi = np.minimum(lo, v - bound), np.maximum(hi, v + bound)
    return val, lo, hi, bool(pending)
