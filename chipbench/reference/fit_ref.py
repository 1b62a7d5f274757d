"""Plain reference for boosting rounds: logistic loss, level-wise depth-D
trees on binned codes, in straightforward ``jax.numpy`` at float32.

It imports nothing of the program.  It follows the documented semantics
(docs/api.md, ``core/splits.py`` module text, XGBoost eq. 7):

* g = sigmoid(m) - y, h = max(sigmoid(m)(1 - sigmoid(m)), 1e-16); the
  first margin is the log-odds of the clipped label mean;
* a node's histogram sums g and h of its records per field and bin; the
  last bin of a field holds its missing values;
* a numeric split ``code <= t`` or a categorical one ``code == c`` sends
  records left, missing values go the better way (left only when that
  gain is strictly larger), gain = (GL²/(HL+λ) + GR²/(HR+λ) - G²/(H+λ))/2
  - γ with both sides' H >= min_child_weight, the first best candidate in
  (field, bin) order wins, and a node splits when its gain is positive
  and its parent split (the root always may);
* records at a node that does not split keep going left, so they end in
  the leftmost bottom slot of its subtree, whose weight -G/(H+λ) from
  those records every slot of the subtree takes;
* leaves are shrunk by the learning rate, and the margins add the leaf
  each record reaches.

Histograms and leaf sums are one-hot contractions at
``Precision.HIGHEST``, in blocks of records so that ten million fit (a
loop over slices: a scan over the records reshaped into blocks took the
TPU compiler five minutes per level at ten million).
``stats="bfloat16"`` rounds g and h to bfloat16 before they are summed,
the step a single-pass MXU histogram would take: that is the control.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 8192                # records per step of the blocked sums


class Tree(NamedTuple):
    feature: np.ndarray       # (2**D - 1,) int, -1 where the node is a leaf
    threshold: np.ndarray
    is_cat: np.ndarray
    default_left: np.ndarray
    leaf_value: np.ndarray    # (2**D,) float32, shrunk


def base_margin(y) -> float:
    p = jnp.clip(jnp.mean(y), 1e-6, 1.0 - 1e-6)
    return float(jnp.log(p / (1.0 - p)))


@jax.jit
def grad_hess(m, y):
    p = 1.0 / (1.0 + jnp.exp(-m))
    return p - y, jnp.maximum(p * (1.0 - p), 1e-16)


@jax.jit
def loss(m, y):
    return jnp.mean(jnp.logaddexp(0.0, m) - y * m)


def _round_stats(g, h, stats: str):
    if stats == "float32":
        return g, h
    if stats == "bfloat16":
        return (g.astype(jnp.bfloat16).astype(jnp.float32),
                h.astype(jnp.bfloat16).astype(jnp.float32))
    raise ValueError(f"unknown statistics precision {stats!r}")


def _blocked_sum(n: int, init, step):
    """Sum ``step(start, fresh)`` over blocks of BLOCK records.  A block
    is sliced in place (no padded copy of the records): the last one is
    moved back to end at record n, and ``fresh`` masks the records an
    earlier block already counted."""
    def body(i, acc):
        start = jnp.minimum(i * BLOCK, max(n - BLOCK, 0))
        fresh = start + jnp.arange(min(BLOCK, n)) >= i * BLOCK
        return acc + step(start, fresh)
    return jax.lax.fori_loop(0, -(-n // BLOCK), body, init)


def _rows(a, start, n: int):
    return jax.lax.dynamic_slice_in_dim(a, start, min(BLOCK, n))


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def level_histogram(codes, g, h, nid, *, n_nodes: int, n_bins: int):
    """(n_nodes, F, n_bins, 2) sums of g and h per node, field and bin."""
    n, F = codes.shape
    nodes = jnp.arange(n_nodes)
    bins = jnp.arange(n_bins)

    def step(start, fresh):
        at = ((_rows(nid, start, n)[:, None] == nodes)
              & fresh[:, None]).astype(jnp.float32)              # (B, NN)
        stats = jnp.concatenate([at * _rows(g, start, n)[:, None],
                                 at * _rows(h, start, n)[:, None]], 1)
        onehot = (_rows(codes, start, n)[:, :, None].astype(jnp.int32)
                  == bins).astype(jnp.float32)                   # (B,F,NB)
        return jnp.einsum("bs,bfk->sfk", stats, onehot, precision=HIGHEST)

    acc = _blocked_sum(n, jnp.zeros((2 * n_nodes, F, n_bins), jnp.float32),
                       step)
    return jnp.stack([acc[:n_nodes], acc[n_nodes:]], axis=-1)


@jax.jit
def best_splits(hist, is_cat_field, lambda_, gamma, min_child_weight):
    """Per node: (gain, feature, threshold, default_left)."""
    NN, F, NB, _ = hist.shape
    G = hist[:, 0, :, 0].sum(-1)[:, None, None]           # node totals
    H = hist[:, 0, :, 1].sum(-1)[:, None, None]
    vals = hist[:, :, :NB - 1, :]
    miss = hist[:, :, NB - 1, :][:, :, None, :]
    cat = is_cat_field[None, :, None]
    left = jnp.where(cat[..., None], vals, jnp.cumsum(vals, axis=2))

    def gain(GL, HL):
        GR, HR = G - GL, H - HL
        ok = (HL >= min_child_weight) & (HR >= min_child_weight)
        val = 0.5 * (GL * GL / (HL + lambda_) + GR * GR / (HR + lambda_)
                     - G * G / (H + lambda_)) - gamma
        return jnp.where(ok, val, -jnp.inf)

    right_gain = gain(left[..., 0], left[..., 1])
    left_gain = gain(left[..., 0] + miss[..., 0],
                     left[..., 1] + miss[..., 1])
    go_left = left_gain > right_gain
    cand = jnp.maximum(left_gain, right_gain).reshape(NN, -1)
    best = jnp.argmax(cand, axis=1)
    g_best = jnp.take_along_axis(cand, best[:, None], 1)[:, 0]
    dl = jnp.take_along_axis(go_left.reshape(NN, -1), best[:, None], 1)
    g_best = jnp.where(jnp.isfinite(g_best), g_best, -1.0)
    return (g_best, (best // (NB - 1)).astype(jnp.int32),
            (best % (NB - 1)).astype(jnp.int32), dl[:, 0].astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("missing_bin",))
def route(codes, nid, feature, threshold, is_cat, default_left, *,
          missing_bin: int):
    """Children ids after one level: 2·node + (0 left, 1 right)."""
    f = feature[nid]
    code = jnp.zeros(nid.shape, jnp.int32)
    for j in range(codes.shape[1]):
        code = jnp.where(f == j, codes[:, j].astype(jnp.int32), code)
    t = threshold[nid]
    left = jnp.where(is_cat[nid] == 1, code == t, code <= t)
    left = jnp.where(code == missing_bin, default_left[nid] == 1, left)
    left = jnp.where(f < 0, True, left)
    return 2 * nid + (1 - left.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_slots",))
def slot_sums(g, h, slot, *, n_slots: int):
    """(n_slots, 2) sums of g and h of the records in each bottom slot."""
    n = g.shape[0]
    slots = jnp.arange(n_slots)

    def step(start, fresh):
        at = ((_rows(slot, start, n)[:, None] == slots)
              & fresh[:, None]).astype(jnp.float32)
        gh = jnp.stack([_rows(g, start, n), _rows(h, start, n)], 1)
        return jnp.einsum("bs,bk->sk", at, gh, precision=HIGHEST)

    return _blocked_sum(n, jnp.zeros((n_slots, 2), jnp.float32), step)


def grow(codes, g, h, *, depth: int, n_bins: int, is_cat_field,
         lambda_: float, gamma: float, min_child_weight: float,
         learning_rate: float):
    """One tree: (Tree, bottom slot of every record, shrunk leaves by
    slot, every level's histogram)."""
    n = codes.shape[0]
    nid = jnp.zeros((n,), jnp.int32)
    levels, hists = [], []
    parent_split = None
    for level in range(depth):
        nn = 2 ** level
        hist = level_histogram(codes, g, h, nid, n_nodes=nn, n_bins=n_bins)
        hists.append(np.asarray(hist))
        gain, feat, thr, dl = best_splits(hist, is_cat_field, lambda_,
                                          gamma, min_child_weight)
        split = gain > 0.0
        if parent_split is not None:
            split = split & jnp.repeat(parent_split, 2)
        feat = jnp.where(split, feat, -1)
        cat = is_cat_field[jnp.maximum(feat, 0)].astype(jnp.int32)
        nid = route(codes, nid, feat, thr, cat, dl, missing_bin=n_bins - 1)
        levels.append((feat, thr, cat, dl))
        parent_split = split
    sums = slot_sums(g, h, nid, n_slots=2 ** depth)
    weight = -sums[:, 0] / (sums[:, 1] + lambda_)
    # a node that stopped holds its records in its leftmost bottom slot;
    # the topmost stopped node on a slot's path decides its weight
    j = jnp.arange(2 ** depth)
    anchor = j
    for level in reversed(range(depth)):
        shift = depth - level
        stopped = levels[level][0][j >> shift] < 0
        anchor = jnp.where(stopped, (j >> shift) << shift, anchor)
    leaf = (weight[anchor] * learning_rate).astype(jnp.float32)
    cols = [np.concatenate([np.asarray(lv[i]) for lv in levels])
            for i in range(4)]
    return Tree(*cols, np.asarray(leaf)), nid, leaf, hists


@jax.jit
def _add_leaves(m, leaf, slot):
    return m + leaf[slot]


def fit_rounds(codes, y, *, rounds: int, depth: int, n_bins: int,
               is_cat_field, lambda_: float, gamma: float,
               min_child_weight: float, learning_rate: float,
               stats: str = "float32", fault: str = "") -> Dict:
    """``rounds`` boosting rounds from the base margin.  Returns the trees
    and the mean loss after each round.

    ``fault`` plants one of the faults the comparison must catch, for the
    readings that set its limits: ``"unchanged"`` (margins never
    updated), ``"half_batch"`` (odd records left out, the rest counted
    twice), ``"altered_leaf"`` (one leaf of the first tree off by 1%)."""
    is_cat_field = jnp.asarray(is_cat_field)
    m = jnp.full(y.shape, base_margin(y), jnp.float32)
    trees: List[Tree] = []
    hists: List[List[np.ndarray]] = []
    losses: List[float] = []
    keep = None
    if fault == "half_batch":
        keep = 2.0 * (jnp.arange(y.shape[0]) % 2 == 0)
    for r in range(rounds):
        g, h = grad_hess(m, y)
        g, h = _round_stats(g, h, stats)
        if keep is not None:
            g, h = g * keep, h * keep
        tree, slot, leaf, hist = grow(codes, g, h, depth=depth,
                                      n_bins=n_bins,
                                is_cat_field=is_cat_field, lambda_=lambda_,
                                gamma=gamma,
                                min_child_weight=min_child_weight,
                                learning_rate=learning_rate)
        if fault == "altered_leaf" and r == 0:
            leaf = leaf.at[0].multiply(1.01)
            tree = tree._replace(leaf_value=np.asarray(leaf))
        if fault != "unchanged":
            m = _add_leaves(m, leaf, slot)
        trees.append(tree)
        hists.append(hist)
        losses.append(float(loss(m, y)))
    params = {"is_cat_field": np.asarray(is_cat_field), "lambda_": lambda_,
              "gamma": gamma, "min_child_weight": min_child_weight}
    return {"trees": trees, "losses": losses, "hists": hists,
            "params": params}


def node_gains(hist, is_cat_field, lambda_, gamma, min_child_weight):
    """Gain of every candidate split of one node, in float64 from its
    (F, NB, 2) histogram: (F, NB-1, 2), the last axis the missing values'
    way (0 right, 1 left); -inf where a side is too light."""
    hist = np.asarray(hist, np.float64)
    NB = hist.shape[1]
    G, H = hist[0, :, 0].sum(), hist[0, :, 1].sum()
    vals, miss = hist[:, :NB - 1], hist[:, NB - 1][:, None, :]
    left = np.where(np.asarray(is_cat_field)[:, None, None], vals,
                    np.cumsum(vals, axis=1))

    def gain(GL, HL):
        GR, HR = G - GL, H - HL
        ok = (HL >= min_child_weight) & (HR >= min_child_weight)
        val = 0.5 * (GL * GL / (HL + lambda_) + GR * GR / (HR + lambda_)
                     - G * G / (H + lambda_)) - gamma
        return np.where(ok, val, -np.inf)

    return np.stack([gain(left[..., 0], left[..., 1]),
                     gain(left[..., 0] + miss[..., 0],
                          left[..., 1] + miss[..., 1])], axis=-1)


def side_hessians(hist, feature: int, threshold: int, is_cat: int,
                  default_left: int):
    """Hessian sums of the two sides of one split of a node, each summed
    from the node's (F, NB, 2) histogram bins directly: a side that no
    record reaches sums to exactly 0."""
    h = np.asarray(hist, np.float64)[feature, :, 1]
    vals, miss = h[:-1], h[-1]
    bins = np.arange(vals.size)
    left = (bins == threshold) if is_cat else (bins <= threshold)
    HL, HR = vals[left].sum(), vals[~left].sum()
    return (HL + miss, HR) if default_left else (HL, HR + miss)


def _same_split(a: Tree, b: Tree, i: int) -> bool:
    if a.feature[i] != b.feature[i]:
        return False
    return bool(b.feature[i] < 0 or (
        a.threshold[i] == b.threshold[i] and a.is_cat[i] == b.is_cat[i]
        and a.default_left[i] == b.default_left[i]))


def compare(trees: List[Tree], losses: List[float], ref: Dict) -> Dict:
    """The numbers compared, program (or stand-in) against the reference.

    Two splits whose gains lie within float32 rounding of each other are
    both right: near the best threshold of a smooth gain curve the next
    bin's gain differs in the last digits, and summing in another order
    can pick either.  So a split that differs from the reference's is
    judged by its gain on the reference's histogram of that node:

    * ``split_shortfall``: the widest relative shortfall of a differing
      split's gain below the best one (not splitting gains 0; infinite
      where a side of the chosen split is lighter than min_child_weight),
      over the nodes whose ancestors split alike.  It is reported, not
      compared: at a node whose best gain is small against its children's
      terms, float32 sums over millions of records move the gains of
      candidates, and of a nearly empty side, by more than that gain;
    * ``leaf_gap``: the widest gap of a leaf whose whole path splits
      alike, each against the larger of that leaf's and the tree's median
      leaf magnitude; a tree with a differing split is the last one whose
      leaves are compared, since the next is grown from other margins;
    * ``loss_gap``: the widest relative gap of the loss after a round;
    * ``light_splits``: how many of the splits at nodes reached alike send
      one side less hessian than ``min_child_weight`` (less 1e-4 of it
      for rounding), summed on the reference's histogram.

    ``counts`` says how many splits differed and how many leaves and
    trees were compared."""
    p = ref["params"]
    shortfall, leaf_gap = 0.0, 0.0
    differing, leaves, compared, light = 0, 0, 0, 0
    floor = p["min_child_weight"] * (1.0 - 1e-4)
    for a, b, hists in zip(trees, ref["trees"], ref["hists"]):
        compared += 1
        depth = len(hists)
        agree = np.zeros(2 ** depth - 1, bool)
        for i in range(agree.size):
            if i and not agree[(i - 1) // 2]:
                continue                       # reached differently
            level = (i + 1).bit_length() - 1
            hist = hists[level][i - (2 ** level - 1)]
            if a.feature[i] >= 0 and min(side_hessians(
                    hist, a.feature[i], a.threshold[i], a.is_cat[i],
                    a.default_left[i])) < floor:
                light += 1
            agree[i] = _same_split(a, b, i)
            if agree[i]:
                continue
            differing += 1
            gains = node_gains(hist, p["is_cat_field"], p["lambda_"],
                               p["gamma"], p["min_child_weight"])
            best = max(float(gains.max()), 0.0)
            chosen = (float(gains[a.feature[i], a.threshold[i],
                                  a.default_left[i]])
                      if a.feature[i] >= 0 else 0.0)
            if not np.isfinite(chosen):        # a side below min_child_weight
                shortfall = np.inf
            else:
                shortfall = max(shortfall, (best - chosen) / max(
                    abs(best), abs(chosen), 1e-30))
        lb = np.asarray(b.leaf_value, np.float64)
        la = np.asarray(a.leaf_value, np.float64)
        scale = np.maximum(np.abs(lb), np.median(np.abs(lb)))
        scale = np.where(scale > 0, scale, 1.0)
        for j in range(lb.size):
            path = [(2 ** lv - 1) + (j >> (depth - lv))
                    for lv in range(depth)]
            if agree[path].all():
                leaves += 1
                leaf_gap = max(leaf_gap, float(abs(la[j] - lb[j]) / scale[j]))
        if not agree.all():
            break
    loss_gap = max(abs(p_ - q) / abs(q) for p_, q in zip(losses,
                                                         ref["losses"]))
    return {"split_shortfall": shortfall, "leaf_gap": leaf_gap,
            "loss_gap": float(loss_gap), "light_splits": light,
            "counts": {"differing_splits": differing,
                       "leaves_compared": leaves,
                       "trees_compared": compared}}
