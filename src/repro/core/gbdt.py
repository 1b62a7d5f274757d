"""Gradient-boosted decision trees — the end-to-end trainer (steps ①–⑥).

The outer loop follows Table I of the paper: grow trees one at a time
(step ⑥), each tree level-by-level (steps ①–④), then pass every record
through the finished tree to refresh its gradient statistics and the total
loss (step ⑤).  The loop is host-driven; each step body is a jitted JAX
function, so the same trainer runs single-device (this container) or under
a pjit mesh (``repro.distributed``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.plan import ExecutionPlan
from repro.core import binning as binning_mod
from repro.core import losses as losses_mod
from repro.core import tree as tree_mod
from repro.core.binning import BinnedDataset
from repro.kernels import ops
from repro.kernels.ref import TreeArrays
from repro.resilience import metrics as _metrics
from repro.resilience.errors import (NumericalDivergenceError,
                                     TrainingInterrupted)
from repro.resilience.recovery import RecoveryPolicy, classify
from repro.resilience.retry import RetryingSource
from repro.resilience.shutdown import GracefulShutdown


@dataclasses.dataclass(frozen=True)
class GBDTConfig:
    """Training hyper-parameters (XGBoost-compatible naming where possible)."""

    n_trees: int = 100
    max_depth: int = 6               # the paper trains 500 x depth-6 trees
    learning_rate: float = 0.1      # shrinkage
    lambda_: float = 1.0             # L2 weight regularization
    gamma: float = 0.0               # per-split complexity penalty
    min_child_weight: float = 1.0
    objective: str = "reg:squarederror"
    subsample: float = 1.0           # stochastic GB (Friedman 2002)
    colsample_bytree: float = 1.0
    goss_top_rate: float = 0.0       # GOSS: kept fraction by |gradient|
    goss_other_rate: float = 0.0     # GOSS: sampled fraction of the rest
    grow_policy: str = "depthwise"   # "depthwise" | "lossguide"
    max_leaves: Optional[int] = None  # lossguide only
    fused_rounds: bool = False       # one jitted step per boosting round:
    #                                  grow + leaf settle + margin update +
    #                                  loss accumulate, margins donated,
    #                                  history fetched every log_every rounds
    log_every: int = 10              # host-fetch / verbose cadence (rounds)
    # deprecated per-step strategy strings — one release path; set an
    # ExecutionPlan (train(plan=...) / fit(plan=...)) instead
    hist_strategy: str = "auto"      # see repro.api.plan.HIST_STRATEGIES
    partition_strategy: str = "auto"
    traversal_strategy: str = "auto"
    host_offload_split: bool = False  # the paper's step-② offload
    early_stopping_rounds: Optional[int] = None
    n_classes: Optional[int] = None  # multi:softmax only; K trees per round
    seed: int = 0

    def __post_init__(self):
        if (self.hist_strategy != "auto"
                or self.partition_strategy != "auto"
                or self.traversal_strategy != "auto"
                or self.host_offload_split):
            warnings.warn(
                "legacy strategy-string kwargs are deprecated; "
                "GBDTConfig's hist_strategy / partition_strategy / "
                "traversal_strategy / host_offload_split fields move to "
                "ExecutionPlan — pass plan=ExecutionPlan(...) to "
                "train()/fit() instead", DeprecationWarning, stacklevel=3)
        if self.max_depth < 1 or self.max_depth > 10:
            raise ValueError("max_depth must be in [1, 10]")
        if self.grow_policy not in ("depthwise", "lossguide"):
            raise ValueError(f"unknown grow_policy {self.grow_policy!r}")
        if self.log_every < 1:
            raise ValueError("log_every must be >= 1")
        if self.fused_rounds and self.grow_policy != "depthwise":
            raise ValueError("fused_rounds requires the depthwise "
                             "grow_policy (lossguide growth is host-driven)")
        if self.goss_top_rate or self.goss_other_rate:
            if not (0.0 <= self.goss_top_rate < 1.0
                    and 0.0 < self.goss_other_rate <= 1.0
                    and self.goss_top_rate + self.goss_other_rate <= 1.0):
                raise ValueError(
                    "GOSS rates need 0 <= top_rate < 1, 0 < other_rate <= 1 "
                    f"and top+other <= 1; got top={self.goss_top_rate}, "
                    f"other={self.goss_other_rate}")
        if self.objective in losses_mod.MULTICLASS_OBJECTIVES:
            if self.n_classes is None or self.n_classes < 2:
                raise ValueError(
                    f"objective {self.objective!r} requires n_classes >= 2")
            if self.grow_policy != "depthwise":
                raise ValueError("multi-class training supports only the "
                                 "depthwise grow_policy")
        elif self.n_classes not in (None, 1):
            raise ValueError(
                f"n_classes={self.n_classes} only applies to multi-class "
                f"objectives, not {self.objective!r}")


@dataclasses.dataclass
class GBDTModel:
    """A trained ensemble: stacked fixed-shape trees + prediction metadata.

    Multi-class ensembles (``n_classes > 1``) stack trees round-major —
    the tree at index ``r * K + k`` belongs to boosting round r, class k —
    and ``base_margin`` is a (K,) per-class vector; margins gain a class
    axis: ``predict_margin`` returns (n, K).
    """

    trees: TreeArrays            # stacked (T, ...) arrays
    base_margin: float           # scalar, or (K,) array when n_classes > 1
    objective: str
    missing_bin: int
    n_fields: int
    max_depth: int
    n_classes: int = 1

    @property
    def n_trees(self) -> int:
        return int(self.trees.feature.shape[0])

    @property
    def n_rounds(self) -> int:
        """Boosting rounds (== n_trees for scalar objectives)."""
        return self.n_trees // max(self.n_classes, 1)

    @property
    def loss(self) -> losses_mod.Loss:
        return losses_mod.get_loss(
            self.objective, self.n_classes if self.n_classes > 1 else None)

    def predict_margin(self, codes, strategy: Optional[str] = None, *,
                       plan: Optional[ExecutionPlan] = None,
                       cached: Optional[bool] = None,
                       mode: Optional[str] = None,
                       cache=None) -> jax.Array:
        """Raw ensemble margins for binned ``codes``.

        ``mode`` is the ONE dispatch knob for the predict surface:

        * ``"direct"`` (default) — dispatch on the exact request shape;
          what training-internal callers want.
        * ``"cached"`` — route through the compile-once predict engine
          (:func:`repro.core.inference.predict_margin_cached`): rows and
          tree count are padded to power-of-two buckets so repeated calls
          with varying batch sizes reuse one compiled step per bucket —
          the serving path.  ``cache`` (a
          :class:`~repro.core.inference.PredictCache`) selects the step
          namespace; ``None`` uses the process-wide default.

        The boolean ``cached=`` flag and the positional ``strategy``
        string are deprecated spellings of the same choices (see
        ``docs/api.md`` for the migration table).
        """
        codes = codes.codes if isinstance(codes, BinnedDataset) else codes
        plan = self._resolve_plan(plan, strategy)
        mode = self._resolve_mode(mode, cached)
        if mode == "cached" and plan.mesh is None:
            from repro.core.inference import predict_margin_cached
            return predict_margin_cached(self, codes, plan=plan,
                                         cache=cache)
        out = ops.predict_ensemble(self.trees, codes,
                                   missing_bin=self.missing_bin,
                                   depth=self.max_depth, plan=plan,
                                   n_classes=self.n_classes)
        if self.n_classes > 1:
            return out + jnp.asarray(self.base_margin, jnp.float32)
        return out + self.base_margin

    def predict(self, codes, strategy: Optional[str] = None, *,
                plan: Optional[ExecutionPlan] = None,
                cached: Optional[bool] = None,
                mode: Optional[str] = None, cache=None) -> jax.Array:
        """Transformed predictions — same surface as :meth:`predict_margin`."""
        return self.loss.transform(
            self.predict_margin(codes, strategy, plan=plan, cached=cached,
                                mode=mode, cache=cache))

    @staticmethod
    def _resolve_mode(mode: Optional[str],
                      cached: Optional[bool]) -> str:
        if cached is not None:
            warnings.warn(
                'cached= is deprecated; use mode="cached" or '
                'mode="direct" instead', DeprecationWarning, stacklevel=3)
            if mode is None:
                mode = "cached" if cached else "direct"
        mode = mode if mode is not None else "direct"
        if mode not in ("cached", "direct"):
            raise ValueError(f"unknown predict mode {mode!r}; choose "
                             "'cached' or 'direct'")
        return mode

    @staticmethod
    def _resolve_plan(plan: Optional[ExecutionPlan],
                      strategy: Optional[str]) -> ExecutionPlan:
        """Model-level lifting of the pre-plan positional ``strategy``
        string (deprecated — one release path, then plans only)."""
        base = plan if plan is not None else ExecutionPlan()
        if strategy is not None and strategy != "auto":
            warnings.warn(
                "legacy strategy-string kwargs are deprecated; pass "
                "plan=ExecutionPlan(traversal_strategy=...) instead",
                DeprecationWarning, stacklevel=4)
            base = base.replace(traversal_strategy=strategy)
        return base.resolved()

    # -- (de)serialization for checkpointing ------------------------------
    def meta(self) -> Dict:
        """JSON-safe model metadata — the ONE encoding shared by state
        dicts, bundles and step checkpoints (see ``model_from_meta``)."""
        return {
            "base_margin": pack_base_margin(self.base_margin,
                                            self.n_classes),
            "objective": self.objective,
            "missing_bin": int(self.missing_bin),
            "n_fields": int(self.n_fields),
            "max_depth": int(self.max_depth),
            "n_classes": int(self.n_classes),
        }

    def to_state(self) -> Dict:
        return {
            "trees": {k: np.asarray(v) for k, v in self.trees._asdict().items()},
            "meta": self.meta(),
        }

    @classmethod
    def from_state(cls, state: Dict) -> "GBDTModel":
        trees = TreeArrays(**{k: jnp.asarray(v)
                              for k, v in state["trees"].items()})
        return model_from_meta(trees, state["meta"])


def pack_base_margin(base_margin, n_classes: int):
    """JSON-safe base margin: per-class float list for K > 1, bare float
    otherwise."""
    if n_classes > 1:
        return [float(b) for b in np.asarray(base_margin)]
    return float(base_margin)


def unpack_base_margin(value, n_classes: int):
    return (np.asarray(value, np.float32) if n_classes > 1
            else float(value))


def model_from_meta(trees: TreeArrays, m: Dict) -> GBDTModel:
    """Rebuild a model from its JSON meta (``GBDTModel.meta``); states
    written before multi-class support carry no n_classes key (K = 1)."""
    K = int(m.get("n_classes", 1))
    # checkpoint restore round-trips scalars through numpy — coerce
    return GBDTModel(trees=trees,
                     base_margin=unpack_base_margin(m["base_margin"], K),
                     objective=str(m["objective"]),
                     missing_bin=int(m["missing_bin"]),
                     n_fields=int(m["n_fields"]),
                     max_depth=int(m["max_depth"]),
                     n_classes=K)


def _stack_trees(trees: List[TreeArrays]) -> TreeArrays:
    return TreeArrays(*[jnp.stack([getattr(t, f) for t in trees])
                        for f in TreeArrays._fields])


def _stack_forests(forests: List[TreeArrays]) -> TreeArrays:
    """Stack per-round (K, ...) forests into round-major (R*K, ...) trees."""
    stacked = _stack_trees(forests)                  # (R, K, ...)
    return TreeArrays(*[a.reshape((-1,) + a.shape[2:]) for a in stacked])


def _unstack_forests(trees: TreeArrays, n_rounds: int,
                     n_classes: int) -> List[TreeArrays]:
    """Invert ``_stack_forests``: (R*K, ...) -> R forests of (K, ...)."""
    resh = [a.reshape((n_rounds, n_classes) + a.shape[1:]) for a in trees]
    return [TreeArrays(*[a[r] for a in resh]) for r in range(n_rounds)]


@dataclasses.dataclass
class TrainResult:
    model: GBDTModel
    history: Dict[str, List[float]]
    step_times: Dict[str, float]     # accumulated seconds per paper step
    stats: Dict = dataclasses.field(default_factory=dict)  # trainer extras
    # streaming fits populate stats with the chunking evidence:
    # n_rows, chunk_rows, n_chunks, passes_per_round


def goss_weights(g, key, top_rate: float, other_rate: float) -> jax.Array:
    """Gradient-based One-Side Sampling weights (LightGBM-style GOSS).

    Keeps the top ``top_rate`` fraction of records by gradient magnitude
    at weight 1, uniformly samples ``other_rate``·n of the rest at weight
    ``(1 - top_rate) / other_rate`` (amplified so the small-gradient
    population keeps its expected contribution to BOTH g and h — the
    hessian reweighting), and drops everything else at weight 0.  ``g`` is
    (n,) or (n, K); multi-class records rank by summed per-class |g|.
    """
    score = jnp.abs(g) if g.ndim == 1 else jnp.sum(jnp.abs(g), axis=-1)
    n = score.shape[0]
    n_top = min(int(np.ceil(top_rate * n)), n)
    n_other = min(int(np.ceil(other_rate * n)), n - n_top)
    order = jnp.argsort(-score)
    w = jnp.zeros((n,), jnp.float32).at[order[:n_top]].set(1.0)
    if n_other > 0:
        rest = order[n_top:]
        pick = jax.random.choice(key, rest.shape[0], (n_other,),
                                 replace=False)
        w = w.at[rest[pick]].set((1.0 - top_rate) / other_rate)
    return w


def _round_stats(config: GBDTConfig, tkey, g, h, n: int, F: int,
                 K: Optional[int]):
    """Per-round stochastic filters on the gradient statistics: GOSS,
    row subsampling, and the per-tree field mask.  Shared verbatim by the
    in-memory and streaming trainers (identical RNG folds), so the two
    paths draw identical samples for identical seeds."""
    if config.goss_top_rate or config.goss_other_rate:
        w = goss_weights(g, jax.random.fold_in(tkey, 2),
                         config.goss_top_rate, config.goss_other_rate)
        if K is not None:
            w = w[:, None]
        g, h = g * w, h * w
    if config.subsample < 1.0:
        mask = (jax.random.uniform(jax.random.fold_in(tkey, 0), (n,))
                < config.subsample).astype(jnp.float32)
        if K is not None:          # same record draw for every class
            mask = mask[:, None]
        g, h = g * mask, h * mask
    if config.colsample_bytree < 1.0:
        field_mask = (jax.random.uniform(jax.random.fold_in(tkey, 1),
                                         (F,)) < config.colsample_bytree)
        field_mask = field_mask.at[jnp.argmax(field_mask)].set(True)
    else:
        field_mask = jnp.ones((F,), bool)
    return g, h, field_mask


def _validate_multiclass_labels(K: int, y, eval_y=None) -> None:
    """An out-of-range class in either split would otherwise clamp inside
    the softmax loss (silent NaN loss / broken early stopping)."""
    batches = [("training", y)]
    if eval_y is not None:
        batches.append(("eval_set", jnp.asarray(eval_y, jnp.float32)))
    for what, yy in batches:
        if not yy.shape[0]:
            continue
        y_min, y_max = float(jnp.min(yy)), float(jnp.max(yy))
        if (y_max >= K or y_min < 0
                or not bool(jnp.all(yy == jnp.round(yy)))):
            raise ValueError(
                f"multi-class {what} labels must be integers in "
                f"[0, {K}); observed range [{y_min}, {y_max}]")


# --------------------------------------------------------------------------
# fused boosting rounds: one jitted step per round, margins donated
# --------------------------------------------------------------------------
def _fused_step_key(config: GBDTConfig) -> GBDTConfig:
    """Strip the fields that do not shape the compiled round (loop
    controls like seed/n_trees/early stopping, and the legacy strategy
    strings already lifted into the plan) so e.g. a seed sweep or CV
    loop reuses ONE compiled step instead of retracing per config."""
    return dataclasses.replace(
        config, n_trees=1, seed=0, early_stopping_rounds=None, log_every=1,
        max_leaves=None, hist_strategy="auto", partition_strategy="auto",
        traversal_strategy="auto", host_offload_split=False)


def shrink(tree: TreeArrays, learning_rate: float) -> TreeArrays:
    """Fold the shrinkage into the leaves (a tree is then self-contained:
    predict == sum of tree outputs, XGBoost-style).

    The product is rounded to float32 before anything adds it: the
    optimization barrier keeps XLA from contracting ``leaf * lr`` with a
    margin add into one FMA, so a fused program rounds like the host loop
    and the resume replay, which add the stored leaf."""
    return tree._replace(leaf_value=jax.lax.optimization_barrier(
        tree.leaf_value * learning_rate))


@functools.lru_cache(maxsize=64)
def _fused_round_step(config: GBDTConfig, plan: ExecutionPlan, n: int,
                      F: int, n_bins: int, n_eval: Optional[int]):
    """Compile one boosting round as a single jitted step.

    The step fuses the whole round — gradient statistics, per-round
    stochastic filters, tree growth (steps ①–④), leaf shrinkage, step-⑤
    margin refresh and the device-side loss reduction — so the host
    dispatches once per round and never synchronizes on intermediate
    values.  Margins (train and eval) are donated where the backend
    supports donation, so the round updates them in place.  Cached per
    (``_fused_step_key(config)``, plan, shapes): repeated fits reuse the
    compiled step.
    """
    loss = losses_mod.get_loss(config.objective, config.n_classes)
    K = loss.n_outputs
    with_eval = n_eval is not None

    def body(margins, y, tkey, codes, codes_cm, is_cat_field):
        g, h = loss.grad_hess(margins, y)
        g, h, field_mask = _round_stats(config, tkey, g, h, n, F, K)
        common = dict(depth=config.max_depth, n_bins=n_bins,
                      missing_bin=n_bins - 1, is_cat_field=is_cat_field,
                      field_mask=field_mask, lambda_=config.lambda_,
                      gamma=config.gamma,
                      min_child_weight=config.min_child_weight, plan=plan)
        if K is not None:
            tree = tree_mod.fit_forest(codes, codes_cm, g.T, h.T, **common)
        else:
            tree = tree_mod.fit_tree(codes, codes_cm, g, h, **common)
        tree = shrink(tree, config.learning_rate)
        data = BinnedDataset(codes, codes_cm, is_cat_field, n_bins,
                             None, None)
        delta = (_predict_forest(tree, data, plan) if K is not None
                 else _predict_one_tree(tree, data, plan))
        margins = margins + delta
        return margins, tree, jnp.mean(loss.value(margins, y))

    if not with_eval:
        step = body
        donate = (0,)
    else:
        def step(margins, ev_margins, y, y_ev, tkey, codes, codes_cm,
                 ev_codes, ev_codes_cm, is_cat_field):
            margins, tree, train_loss = body(margins, y, tkey, codes,
                                             codes_cm, is_cat_field)
            ev_data = BinnedDataset(ev_codes, ev_codes_cm, is_cat_field,
                                    n_bins, None, None)
            ev_delta = (_predict_forest(tree, ev_data, plan)
                        if K is not None
                        else _predict_one_tree(tree, ev_data, plan))
            ev_margins = ev_margins + ev_delta
            ev_loss = jnp.mean(loss.value(ev_margins, y_ev))
            return margins, ev_margins, tree, train_loss, ev_loss
        donate = (0, 1)
    # donation is a no-op (plus a warning) on the CPU backend — only ask
    # for it where XLA actually aliases the buffers
    if jax.default_backend() not in ("tpu", "gpu"):
        donate = ()
    return jax.jit(step, donate_argnums=donate)


def train(config: GBDTConfig, data: BinnedDataset, y,
          eval_set: Optional[Tuple[BinnedDataset, jax.Array]] = None,
          init_model: Optional[GBDTModel] = None,
          callback: Optional[Callable[[int, GBDTModel], None]] = None,
          verbose: bool = False,
          plan: Optional[ExecutionPlan] = None,
          recovery: Optional[RecoveryPolicy] = None,
          shutdown: Optional[GracefulShutdown] = None) -> TrainResult:
    """Fit a GBDT ensemble.  Deterministic per-tree RNG (fault-replayable).

    ``plan`` selects the kernel strategies for every step; when omitted it
    is lifted from the config's legacy per-step strategy strings.

    ``recovery`` arms the numerical divergence sentinels: a non-finite
    loss/margin caught every ``config.log_every`` rounds rolls the fused
    fit back to the last finite round (learning-rate backoff when the
    same round diverges twice, bounded by
    ``recovery.max_divergence_rollbacks``); without a policy the sentinel
    raises :class:`NumericalDivergenceError` fail-fast.  ``shutdown``
    (a :class:`repro.resilience.GracefulShutdown`) makes the fit
    preemption-safe: a delivered signal finishes the in-flight round,
    commits it, and raises :class:`TrainingInterrupted` carrying the
    partial :class:`TrainResult`.
    """
    if plan is None:
        plan = ExecutionPlan.from_config(config)
    plan = plan.resolved()
    if plan.mesh is not None:
        # a training mesh routes the whole fit through the data-parallel
        # engine (records sharded over plan.data_axes, one histogram psum
        # per level) — see repro.distributed.trainer
        from repro.distributed.trainer import train_distributed
        return train_distributed(config, data, y, eval_set=eval_set,
                                 init_model=init_model, callback=callback,
                                 verbose=verbose, plan=plan,
                                 recovery=recovery, shutdown=shutdown)
    loss = losses_mod.get_loss(config.objective, config.n_classes)
    K = loss.n_outputs                 # None for scalar objectives
    y = jnp.asarray(y, jnp.float32)
    if K is not None:
        _validate_multiclass_labels(
            K, y, eval_set[1] if eval_set is not None else None)
    n, F = data.codes.shape
    depth = config.max_depth

    trees: List[TreeArrays] = []       # one entry per round; multi-class
    history: Dict[str, List[float]] = {"train_loss": []}   # entries: (K,...)
    if eval_set is not None:
        history["eval_loss"] = []
    step_times = {"binning_split": 0.0, "partition": 0.0, "traversal": 0.0,
                  "other": 0.0}

    if init_model is not None:
        if K is not None:
            trees = _unstack_forests(init_model.trees, init_model.n_rounds,
                                     K)
        else:
            trees = [TreeArrays(*[a[i] for a in init_model.trees])
                     for i in range(init_model.n_trees)]
        base_margin = init_model.base_margin
        margins = _replay_margins(init_model, data, plan)
        eval_margins = (_replay_margins(init_model, eval_set[0], plan)
                        if eval_set is not None else None)
    elif K is not None:
        base_margin = np.asarray(loss.base_margin(y), np.float32)  # (K,)
        margins = jnp.broadcast_to(jnp.asarray(base_margin), (n, K))
        eval_margins = (jnp.broadcast_to(jnp.asarray(base_margin),
                                         (eval_set[1].shape[0], K))
                        if eval_set is not None else None)
    else:
        base_margin = float(loss.base_margin(y))
        margins = jnp.full((n,), base_margin, jnp.float32)
        eval_margins = (jnp.full((eval_set[1].shape[0],), base_margin)
                        if eval_set is not None else None)

    key = jax.random.PRNGKey(config.seed)
    best_eval, best_round = np.inf, -1

    if config.fused_rounds:
        return _train_fused(config, plan, data, y, eval_set, trees, margins,
                            eval_margins, base_margin, history, step_times,
                            key, callback, verbose, n, F,
                            recovery=recovery, shutdown=shutdown)

    start = len(trees)
    for t_idx in range(start, start + config.n_trees):
        tkey = jax.random.fold_in(key, t_idx)  # deterministic replay stream
        t0 = time.perf_counter()
        g, h = loss.grad_hess(margins, y)
        g, h, field_mask = _round_stats(config, tkey, g, h, n, F, K)

        common = dict(depth=depth, n_bins=data.n_bins,
                      missing_bin=data.missing_bin,
                      is_cat_field=data.is_categorical,
                      field_mask=field_mask, lambda_=config.lambda_,
                      gamma=config.gamma,
                      min_child_weight=config.min_child_weight, plan=plan)
        if K is not None:
            # one class-batched pass grows all K per-class trees
            tree = tree_mod.fit_forest(data.codes, data.codes_cm,
                                       g.T, h.T, **common)
        elif config.grow_policy == "depthwise":
            tree = tree_mod.fit_tree(data.codes, data.codes_cm, g, h,
                                     **common)
        else:
            tree = tree_mod.fit_tree_lossguide(
                data.codes, data.codes_cm, g, h,
                max_leaves=config.max_leaves, **common)
        # shrinkage is folded into the stored leaf values so a tree is
        # self-contained (predict == sum of tree outputs, XGBoost-style)
        tree = shrink(tree, config.learning_rate)
        tree = jax.tree.map(jax.block_until_ready, tree)
        t1 = time.perf_counter()
        step_times["binning_split"] += t1 - t0

        # step ⑤ — one-tree traversal refreshes margins (and thus g, h)
        if K is not None:
            delta = _predict_forest(tree, data, plan)          # (n, K)
        else:
            delta = _predict_one_tree(tree, data, plan)
        margins = margins + delta
        margins.block_until_ready()
        t2 = time.perf_counter()
        step_times["traversal"] += t2 - t1

        trees.append(tree)
        train_loss = float(jnp.mean(loss.value(margins, y)))
        history["train_loss"].append(train_loss)

        if eval_set is not None:
            if K is not None:
                ev_delta = _predict_forest(tree, eval_set[0], plan)
            else:
                ev_delta = _predict_one_tree(tree, eval_set[0], plan)
            eval_margins = eval_margins + ev_delta
            ev = float(jnp.mean(loss.value(eval_margins,
                                           jnp.asarray(eval_set[1],
                                                       jnp.float32))))
            history["eval_loss"].append(ev)
            if ev < best_eval - 1e-12:
                best_eval, best_round = ev, t_idx
            if (config.early_stopping_rounds is not None
                    and t_idx - best_round >= config.early_stopping_rounds):
                if verbose:
                    print(f"[gbdt] early stop at tree {t_idx} "
                          f"(best {best_round}: {best_eval:.6f})")
                break
        step_times["other"] += time.perf_counter() - t2

        if verbose and (t_idx % config.log_every == 0
                        or t_idx == start + config.n_trees - 1):
            print(f"[gbdt] tree {t_idx:4d}  train_loss={train_loss:.6f}")
        # divergence sentinel: the host loop already syncs the loss each
        # round, so the finiteness check is free; the rollback machinery
        # lives in the fused/distributed engines — here the sentinel is
        # fail-fast-but-typed
        if recovery is not None and not np.isfinite(train_loss):
            raise NumericalDivergenceError(
                f"non-finite training loss at round {t_idx}",
                round_index=t_idx, what="loss")
        if callback is not None:
            callback(t_idx, _as_model(trees, base_margin, config,
                                      data.missing_bin, F))
        if shutdown is not None and shutdown.requested:
            partial = TrainResult(
                model=_as_model(trees, base_margin, config,
                                data.missing_bin, F),
                history=history, step_times=step_times,
                stats={"n_rows": n, "interrupted": True})
            raise TrainingInterrupted(
                f"shutdown ({shutdown.signal_name}) after round {t_idx}",
                rounds_done=len(trees), signal_name=shutdown.signal_name,
                result=partial)

    return TrainResult(model=_as_model(trees, base_margin, config,
                                       data.missing_bin, F),
                       history=history, step_times=step_times,
                       stats={"n_rows": n})


def _train_fused(config, plan, data, y, eval_set, trees, margins,
                 eval_margins, base_margin, history, step_times, key,
                 callback, verbose, n, F, recovery=None,
                 shutdown=None) -> TrainResult:
    """The device-resident boosting loop: one jitted dispatch per round.

    The host never synchronizes on per-round values unless it has to —
    losses stay device scalars, fetched every ``config.log_every`` rounds
    for verbose logging and once in bulk at the end.  Early stopping is
    the one per-round consumer: it pulls the eval scalar each round
    (still a single dispatch per round).  Per-step attribution is not
    possible inside a fused round, so wall time lands in a dedicated
    ``fused_rounds`` slot of ``step_times``.

    Divergence sentinel: every ``config.log_every`` rounds one device-side
    ``isfinite`` reduction over (loss, margins) is synced to the host.  A
    trip with a ``recovery`` policy rolls the fit back to the last finite
    sentinel snapshot and replays — at the ORIGINAL learning rate first
    (a transient glitch replays bit-equal), backing the rate off by
    ``recovery.divergence_backoff`` only when the same window diverges
    twice (``learning_rate`` is part of the step cache key, so the
    backoff recompiles the round).  Without a policy the sentinel raises
    :class:`NumericalDivergenceError` fail-fast.
    """
    live = config                      # LR backoff replaces this copy only
    n_eval = None if eval_set is None else int(eval_set[1].shape[0])
    step = _fused_round_step(_fused_step_key(live), plan, n, F,
                             data.n_bins, n_eval)
    y_ev = (jnp.asarray(eval_set[1], jnp.float32)
            if eval_set is not None else None)
    train_dev: List[jax.Array] = []
    eval_dev: List[jax.Array] = []
    best_eval, best_round = np.inf, -1
    rstats = {"divergence_rollbacks": 0}
    t_loop = time.perf_counter()
    start = len(trees)
    end = start + config.n_trees

    def _flush_history():
        # one bulk fetch materializes the whole loss trajectory
        history["train_loss"].extend(float(v)
                                     for v in jax.device_get(train_dev))
        if eval_set is not None:
            history["eval_loss"].extend(float(v)
                                        for v in jax.device_get(eval_dev))
        step_times["fused_rounds"] = time.perf_counter() - t_loop

    def _snap(t_next):
        """Host copy of the resumable loop state (taken only at finite
        sentinel checks, so a rollback always lands on finite state)."""
        return {"t": t_next, "trees": len(trees), "dev": len(train_dev),
                "margins": np.asarray(margins),
                "eval": (None if eval_margins is None
                         else np.asarray(eval_margins)),
                "best": (best_eval, best_round)}

    snap = _snap(start)
    diverged_at = -1                   # sentinel window of the last trip
    t_idx = start
    stop_early = False
    while t_idx < end and not stop_early:
        tkey = jax.random.fold_in(key, t_idx)   # same stream as host loop
        if eval_set is None:
            margins, tree, tl = step(margins, y, tkey, data.codes,
                                     data.codes_cm, data.is_categorical)
        else:
            margins, eval_margins, tree, tl, ev = step(
                margins, eval_margins, y, y_ev, tkey, data.codes,
                data.codes_cm, eval_set[0].codes, eval_set[0].codes_cm,
                data.is_categorical)
            eval_dev.append(ev)
        trees.append(tree)
        train_dev.append(tl)
        if eval_set is not None and config.early_stopping_rounds is not None:
            ev_f = float(ev)                    # the one per-round sync
            if ev_f < best_eval - 1e-12:
                best_eval, best_round = ev_f, t_idx
            if t_idx - best_round >= config.early_stopping_rounds:
                if verbose:
                    print(f"[gbdt] early stop at tree {t_idx} "
                          f"(best {best_round}: {best_eval:.6f})")
                stop_early = True
        if verbose and (t_idx % config.log_every == 0 or t_idx == end - 1):
            print(f"[gbdt] tree {t_idx:4d}  train_loss={float(tl):.6f}")

        # ---- divergence sentinel (one fused device reduction + sync)
        if t_idx % config.log_every == 0 or t_idx == end - 1 or stop_early:
            finite = bool(jnp.isfinite(tl) & jnp.all(jnp.isfinite(margins)))
            if not finite:
                if (recovery is None or rstats["divergence_rollbacks"]
                        >= recovery.max_divergence_rollbacks):
                    raise NumericalDivergenceError(
                        f"non-finite loss/margins at round {t_idx}",
                        round_index=t_idx, what="loss/margins")
                rstats["divergence_rollbacks"] += 1
                _metrics.record("recoveries")
                del trees[snap["trees"]:]
                del train_dev[snap["dev"]:]
                del eval_dev[snap["dev"]:]
                margins = jnp.asarray(snap["margins"])
                eval_margins = (None if snap["eval"] is None
                                else jnp.asarray(snap["eval"]))
                best_eval, best_round = snap["best"]
                if diverged_at == snap["t"]:
                    # the same window diverged on its replay: genuine
                    # divergence, not a glitch — shrink the steps
                    live = dataclasses.replace(
                        live, learning_rate=(live.learning_rate
                                             * recovery.divergence_backoff))
                    step = _fused_round_step(_fused_step_key(live), plan,
                                             n, F, data.n_bins, n_eval)
                    if verbose:
                        print(f"[gbdt] round {snap['t']} diverged twice; "
                              f"learning_rate -> {live.learning_rate:g}")
                elif verbose:
                    print(f"[gbdt] divergence at round {t_idx}; rolling "
                          f"back to round {snap['t']}")
                diverged_at = snap["t"]
                t_idx = snap["t"]
                stop_early = False
                continue
            snap = _snap(t_idx + 1)
        if callback is not None:
            callback(t_idx, _as_model(trees, base_margin, config,
                                      data.missing_bin, F))
        if shutdown is not None and shutdown.requested:
            _flush_history()
            partial = TrainResult(
                model=_as_model(trees, base_margin, config,
                                data.missing_bin, F),
                history=history, step_times=step_times,
                stats={"n_rows": n, "fused_rounds": True,
                       "interrupted": True, **rstats})
            raise TrainingInterrupted(
                f"shutdown ({shutdown.signal_name}) after round {t_idx}",
                rounds_done=len(trees), signal_name=shutdown.signal_name,
                result=partial)
        t_idx += 1
    _flush_history()
    jax.block_until_ready(margins)
    return TrainResult(model=_as_model(trees, base_margin, config,
                                       data.missing_bin, F),
                       history=history, step_times=step_times,
                       stats={"n_rows": n, "fused_rounds": True, **rstats})


def _as_model(trees, base_margin, config, missing_bin, F) -> GBDTModel:
    K = config.n_classes or 1
    stacked = _stack_forests(trees) if K > 1 else _stack_trees(trees)
    return GBDTModel(trees=stacked, base_margin=base_margin,
                     objective=config.objective,
                     missing_bin=missing_bin, n_fields=F,
                     max_depth=config.max_depth, n_classes=K)


def _predict_one_tree(tree: TreeArrays, data: BinnedDataset,
                      plan: ExecutionPlan) -> jax.Array:
    """Step-⑤ traversal, using the paper's renumbered-column fetch when it
    saves bandwidth: a depth-D tree touches ≤ 2^D − 1 columns, so for wide
    datasets only those columns are gathered from the column-major copy."""
    n_int = tree.feature.shape[0]
    F = data.n_fields
    if F > n_int:
        # per-node column fetch: node i's field becomes renumbered column i
        # (unpacks only the <= N_int gathered fields when codes_cm is
        # nibble-packed)
        cols = tree_mod._gather_fields(
            data.codes_cm, jnp.maximum(tree.feature, 0))          # (N_int, n)
        renum = jnp.where(tree.feature >= 0,
                          jnp.arange(n_int, dtype=jnp.int32), -1)
        tree_c = tree._replace(feature=renum)
        return ops.traverse_tree(tree_c, cols.T,
                                 missing_bin=data.missing_bin, plan=plan)
    return ops.traverse_tree(tree, data.codes, missing_bin=data.missing_bin,
                             plan=plan)


def _predict_forest(forest: TreeArrays, data: BinnedDataset,
                    plan: ExecutionPlan) -> jax.Array:
    """Step-⑤ traversal of one round's K per-class trees -> (n, K) deltas."""
    delta = jax.vmap(lambda t: _predict_one_tree(t, data, plan))(forest)
    return delta.T


def _replay_margins(model: GBDTModel, data: BinnedDataset,
                    plan: ExecutionPlan) -> jax.Array:
    """Seed margins for a continued fit by accumulating per-round deltas in
    round order — the SAME order the interrupted fit used — so checkpoint
    resume and warm start replay bit-exactly.  (A single batched
    ``predict_margin`` reduces the tree axis pairwise, which can differ
    from sequential accumulation in the last ulp and would perturb every
    downstream leaf value.)"""
    n = data.codes.shape[0]
    K = model.n_classes
    if K > 1:
        m = jnp.broadcast_to(
            jnp.asarray(model.base_margin, jnp.float32), (n, K))
        for r in range(model.n_rounds):
            forest = TreeArrays(*[a[r * K:(r + 1) * K]
                                  for a in model.trees])
            m = m + _predict_forest(forest, data, plan)
        return m
    m = jnp.full((n,), model.base_margin, jnp.float32)
    for t in range(model.n_trees):
        tree = TreeArrays(*[a[t] for a in model.trees])
        m = m + _predict_one_tree(tree, data, plan)
    return m


# --------------------------------------------------------------------------
# out-of-core training: chunk-streamed histograms, GOSS, sketch binning
# --------------------------------------------------------------------------
def _streamed_margins(model: GBDTModel, chunks, n: int,
                      plan: ExecutionPlan) -> jax.Array:
    """Warm-start margins without materializing the matrix: one chunked
    inference pass, accumulating per-round deltas in round order (the same
    element-wise addition order the interrupted fit used) so checkpoint
    resume replays bit-exactly — see :func:`_replay_margins`."""
    K = model.n_classes
    out = np.zeros((n, K) if K > 1 else (n,), np.float32)
    for lo, hi, codes in chunks():
        rows = codes.n if hasattr(codes, "n") else codes.shape[0]
        if K > 1:
            m = jnp.broadcast_to(
                jnp.asarray(model.base_margin, jnp.float32), (rows, K))
            for r in range(model.n_rounds):
                forest = TreeArrays(*[a[r * K:(r + 1) * K]
                                      for a in model.trees])
                delta = jax.vmap(lambda t: ops.traverse_tree(
                    t, codes, missing_bin=model.missing_bin,
                    plan=plan))(forest)
                m = m + delta.T
        else:
            m = jnp.full((rows,), model.base_margin, jnp.float32)
            for t_i in range(model.n_trees):
                tree = TreeArrays(*[a[t_i] for a in model.trees])
                m = m + ops.traverse_tree(tree, codes,
                                          missing_bin=model.missing_bin,
                                          plan=plan)
        out[lo:hi] = np.asarray(m)[: hi - lo]
    return jnp.asarray(out)


def train_streaming(config: GBDTConfig, source, binner, y, *,
                    eval_set: Optional[Tuple[BinnedDataset, jax.Array]] = None,
                    init_model: Optional[GBDTModel] = None,
                    callback: Optional[Callable[[int, GBDTModel], None]] = None,
                    verbose: bool = False,
                    plan: Optional[ExecutionPlan] = None,
                    chunk_rows: Optional[int] = None,
                    recovery: Optional[RecoveryPolicy] = None,
                    shutdown: Optional[GracefulShutdown] = None
                    ) -> TrainResult:
    """Out-of-core twin of :func:`train`: the binned matrix is NEVER
    materialized — each tree level re-streams device-sized chunks from
    ``source``, accumulating step-① histograms chunk by chunk and keeping
    step-③ node-id vectors chunk-local (``tree.fit_forest_chunked``).
    Host-resident state is per-record scalars only (margins, g/h, node
    ids); device-resident state is one chunk plus the level histogram.

    source:      a :class:`repro.data.DataSource` of raw float chunks;
                 successive passes must yield identical chunks.
    binner:      a fitted ``Binner``/``StreamingBinner`` (chunks are binned
                 on the fly each pass).
    y:           (n,) labels, gathered from the source by the caller.
    eval_set:    optional in-memory ``(BinnedDataset, y_val)`` pair.
    chunk_rows:  records per streamed chunk; defaults to the plan's
                 ``chunk_bytes`` budget (``ExecutionPlan.chunk_rows``).
    recovery:    a :class:`repro.resilience.RecoveryPolicy` enabling
                 self-healing rounds: a transient source failure replays
                 the round (from the newest ``checkpoint_dir`` checkpoint
                 when one exists, else from the in-memory end-of-previous
                 -round state), and a device OOM halves the chunk size
                 and retries — chunked histogram accumulation is
                 chunk-size-invariant, so degradation never changes the
                 model.  Rounds commit state atomically (margins, trees,
                 history all mutate only after the round's compute
                 succeeds), and the per-round RNG is keyed by
                 ``(seed, round)``, so replayed rounds reproduce the
                 fault-free fit.  ``None`` (default) = fail fast.
    shutdown:    a :class:`repro.resilience.GracefulShutdown`; a delivered
                 signal finishes the in-flight round, commits it (plus a
                 final checkpoint when ``recovery.checkpoint_dir`` is
                 set), and raises :class:`TrainingInterrupted` carrying
                 the partial result — ``fit`` resumes from it.

    Per-round data passes: ``max_depth + 1`` (one per level — the previous
    level's partition is applied lazily in the histogram pass — plus one
    final partition pass).  Step ⑤ is free: margins update from the final
    leaf-slot ids, no traversal of the stream.

    GOSS (``config.goss_top_rate`` / ``goss_other_rate``) drops the
    zero-weight record stream from the histogram *stat* volume each round
    while node ids stay maintained for every record, so margins (and the
    next round's gradients) remain exact.

    ``config.fused_rounds`` is ignored here: every round is a host-driven
    chunk pipeline by construction.  ``plan.hist_subtraction`` applies —
    levels > 0 accumulate only smaller-child statistics per chunk and
    derive the sibling histograms once per level.
    """
    if plan is None:
        plan = ExecutionPlan.from_config(config)
    plan = plan.resolved()
    kernel_plan = plan.without_chunking()
    if config.grow_policy != "depthwise":
        raise ValueError("streaming training supports only the depthwise "
                         "grow_policy")
    loss = losses_mod.get_loss(config.objective, config.n_classes)
    K = loss.n_outputs
    y = jnp.asarray(y, jnp.float32)
    if K is not None:
        _validate_multiclass_labels(
            K, y, eval_set[1] if eval_set is not None else None)
    n = int(y.shape[0])
    F = int(source.n_fields)
    depth = config.max_depth
    # resolve the packed-codes layout BEFORE sizing chunks: 4-bit packing
    # halves the per-row code bytes, so the same chunk_bytes budget fits
    # ~2x the records per streamed chunk (paper §III-B)
    if plan.packed_codes is None:
        plan = plan.replace(
            packed_codes=binner.max_bins <= binning_mod.PACK_MAX_BINS)
        kernel_plan = plan.without_chunking()
    elif plan.packed_codes and binner.max_bins > binning_mod.PACK_MAX_BINS:
        raise ValueError(
            f"plan requests 4-bit packed codes but the binner has "
            f"max_bins={binner.max_bins} > {binning_mod.PACK_MAX_BINS}")
    packed = bool(plan.packed_codes)
    if chunk_rows is None:
        chunk_rows = plan.chunk_rows(F, K or 1)
    # never pad past the data: a small dataset under a large byte budget
    # would otherwise stream (and histogram) mostly padding every pass
    chunk_rows = max(1, min(int(chunk_rows), n))
    # mutable so OOM degradation can shrink the streamed chunks mid-fit;
    # each pass reads the cell once at open, so a resize takes effect on
    # the retried round's first pass
    chunk_state = {"rows": chunk_rows}
    missing_bin = binner.max_bins - 1
    is_cat_field = jnp.asarray(binner._is_cat)
    n_chunks = [0]

    def binned_chunks():
        """One full pass: bin + pad (+ 4-bit pack) each raw chunk on the
        host (prefetch thread overlaps binning/transfer with device
        compute), yield ``(lo, hi, codes)`` with a fixed (chunk_rows, F)
        logical device shape — ``codes`` is a :class:`PackedCodes` when
        the plan packs, so each chunk DMAs half the code bytes."""
        from repro.data.pipeline import PrefetchIterator
        rows_now = chunk_state["rows"]

        def gen():
            for X_chunk, _ in source.chunks(rows_now):
                codes = binner.transform_codes(X_chunk)
                n_real = codes.shape[0]
                if n_real > rows_now:
                    raise ValueError(
                        f"source yielded a {n_real}-row chunk for a "
                        f"{rows_now}-row request")
                if n_real < rows_now:
                    codes = np.pad(codes,
                                   ((0, rows_now - n_real), (0, 0)))
                if packed:
                    codes = binning_mod.pack_nibbles_np(codes)
                yield {"rows": np.int32(n_real), "codes": codes}

        lo = 0
        count = 0
        with PrefetchIterator(gen(), depth=2) as batches:
            for batch in batches:
                n_real = int(batch["rows"])
                codes = (binning_mod.PackedCodes(batch["codes"], F)
                         if packed else batch["codes"])
                yield lo, lo + n_real, codes
                lo += n_real
                count += 1
        if lo != n:
            raise ValueError(
                f"source pass yielded {lo} rows but len(y) == {n}; "
                "DataSource passes must be identical and label-complete")
        n_chunks[0] = count

    trees: List[TreeArrays] = []
    history: Dict[str, List[float]] = {"train_loss": []}
    if eval_set is not None:
        history["eval_loss"] = []
    step_times = {"binning_split": 0.0, "partition": 0.0, "traversal": 0.0,
                  "other": 0.0}

    if init_model is not None:
        if K is not None:
            trees = _unstack_forests(init_model.trees, init_model.n_rounds,
                                     K)
        else:
            trees = [TreeArrays(*[a[i] for a in init_model.trees])
                     for i in range(init_model.n_trees)]
        base_margin = init_model.base_margin
        margins = _streamed_margins(init_model, binned_chunks, n,
                                    kernel_plan)
        eval_margins = (init_model.predict_margin(eval_set[0].codes,
                                                  plan=kernel_plan)
                        if eval_set is not None else None)
    elif K is not None:
        base_margin = np.asarray(loss.base_margin(y), np.float32)
        margins = jnp.broadcast_to(jnp.asarray(base_margin), (n, K))
        eval_margins = (jnp.broadcast_to(jnp.asarray(base_margin),
                                         (eval_set[1].shape[0], K))
                        if eval_set is not None else None)
    else:
        base_margin = float(loss.base_margin(y))
        margins = jnp.full((n,), base_margin, jnp.float32)
        eval_margins = (jnp.full((eval_set[1].shape[0],), base_margin)
                        if eval_set is not None else None)

    key = jax.random.PRNGKey(config.seed)
    best_eval, best_round = np.inf, -1

    start = len(trees)
    end = start + config.n_trees
    rstats = {"recoveries": 0, "oom_halvings": 0, "replayed_rounds": 0}
    pending_restore = False

    def _save_round_checkpoint(rounds_done: int) -> None:
        # lazy import: repro.api depends on this module
        from repro.api import serialize
        from repro.core.inference import GBDTPipeline
        model = _as_model(trees, base_margin, config, missing_bin, F)
        serialize.save_checkpoint(recovery.checkpoint_dir,
                                  GBDTPipeline(binner=binner, model=model),
                                  rounds_done)

    def _restore_state():
        """Trainer state from the newest valid checkpoint: trees unstacked
        from the bundled model, margins recomputed with one streamed
        inference pass (so no per-record state needs checkpointing)."""
        from repro.api import serialize
        pipe, _step = serialize.load_checkpoint(recovery.checkpoint_dir)
        model = pipe.model
        if K is not None:
            rtrees = _unstack_forests(model.trees, model.n_rounds, K)
        else:
            rtrees = [TreeArrays(*[a[i] for a in model.trees])
                      for i in range(model.n_trees)]
        rmargins = _streamed_margins(model, binned_chunks, n, kernel_plan)
        rev = (model.predict_margin(eval_set[0].codes, plan=kernel_plan)
               if eval_set is not None else None)
        return rtrees, rmargins, rev, len(rtrees)

    def _stats():
        return {"n_rows": n, "chunk_rows": int(chunk_state["rows"]),
                "n_chunks": int(n_chunks[0]),
                "passes_per_round": depth + 1, **rstats}

    t_idx = t_done = start
    try:
        while t_idx < end:
            try:
                if pending_restore:
                    trees, margins, eval_margins, t_idx = _restore_state()
                    rstats["replayed_rounds"] += max(0, t_done - t_idx)
                    del history["train_loss"][t_idx - start:]
                    if eval_set is not None:
                        del history["eval_loss"][t_idx - start:]
                        evs = history["eval_loss"]
                        best_eval = min(evs) if evs else np.inf
                        best_round = (start + int(np.argmin(evs))) if evs \
                            else -1
                    pending_restore = False

                tkey = jax.random.fold_in(key, t_idx)
                t0 = time.perf_counter()
                g, h = loss.grad_hess(margins, y)
                g, h, field_mask = _round_stats(config, tkey, g, h, n, F, K)
                g2 = np.asarray(g.T if K is not None else g[None],
                                np.float32)
                h2 = np.asarray(h.T if K is not None else h[None],
                                np.float32)

                forest, leaf_ids = tree_mod.fit_forest_chunked(
                    binned_chunks, g2, h2, depth=depth,
                    n_bins=binner.max_bins, missing_bin=missing_bin,
                    is_cat_field=is_cat_field, field_mask=field_mask,
                    lambda_=config.lambda_, gamma=config.gamma,
                    min_child_weight=config.min_child_weight,
                    plan=kernel_plan)
                forest = shrink(forest, config.learning_rate)
                forest = jax.tree.map(jax.block_until_ready, forest)
                t1 = time.perf_counter()

                # step ⑤ for free: the chunk-local node ids END as leaf
                # slots, so the margin refresh is a leaf-value lookup,
                # not a data pass
                delta = jax.vmap(lambda v, i: v[i])(
                    forest.leaf_value, jnp.asarray(leaf_ids))       # (K, n)
                tree = forest if K is not None else TreeArrays(
                    *[a[0] for a in forest])
                new_margins = margins + (delta.T if K is not None
                                         else delta[0])
                new_margins.block_until_ready()
                t2 = time.perf_counter()

                if eval_set is not None:
                    if K is not None:
                        ev_delta = _predict_forest(tree, eval_set[0],
                                                   kernel_plan)
                    else:
                        ev_delta = _predict_one_tree(tree, eval_set[0],
                                                     kernel_plan)
                    new_eval_margins = eval_margins + ev_delta
                    ev = float(jnp.mean(loss.value(
                        new_eval_margins,
                        jnp.asarray(eval_set[1], jnp.float32))))
                else:
                    new_eval_margins, ev = None, None
            except Exception as exc:  # noqa: BLE001 — classified below
                action = classify(exc) if recovery is not None else "fatal"
                if action == "oom":
                    rows = chunk_state["rows"]
                    new_rows = max(recovery.min_chunk_rows, rows // 2)
                    if (new_rows >= rows or rstats["oom_halvings"]
                            >= recovery.max_oom_halvings):
                        raise
                    rstats["oom_halvings"] += 1
                    _metrics.record("recoveries")
                    chunk_state["rows"] = new_rows
                    if verbose:
                        print(f"[gbdt] device OOM at tree {t_idx}: "
                              f"chunk_rows {rows} -> {new_rows}; "
                              "retrying round")
                    continue
                if action == "transient":
                    if rstats["recoveries"] >= recovery.max_recoveries:
                        raise
                    rstats["recoveries"] += 1
                    _metrics.record("recoveries")
                    if recovery.retry_delay_s:
                        time.sleep(recovery.retry_delay_s)
                    if recovery.checkpoint_dir is not None:
                        from repro.api import serialize
                        pending_restore = serialize.has_checkpoint(
                            recovery.checkpoint_dir)
                    if verbose:
                        how = ("restoring newest checkpoint"
                               if pending_restore
                               else "replaying round in memory")
                        print(f"[gbdt] transient failure at tree {t_idx} "
                              f"({type(exc).__name__}: {exc}); {how}")
                    continue
                raise

            # ---- commit: the round succeeded, mutate state atomically
            step_times["binning_split"] += t1 - t0
            step_times["traversal"] += t2 - t1
            margins = new_margins
            trees.append(tree)
            train_loss = float(jnp.mean(loss.value(margins, y)))
            history["train_loss"].append(train_loss)
            stop_early = False

            if eval_set is not None:
                eval_margins = new_eval_margins
                history["eval_loss"].append(ev)
                if ev < best_eval - 1e-12:
                    best_eval, best_round = ev, t_idx
                if (config.early_stopping_rounds is not None
                        and t_idx - best_round
                        >= config.early_stopping_rounds):
                    if verbose:
                        print(f"[gbdt] early stop at tree {t_idx} "
                              f"(best {best_round}: {best_eval:.6f})")
                    stop_early = True
            step_times["other"] += time.perf_counter() - t2

            if verbose and (t_idx % config.log_every == 0
                            or t_idx == end - 1):
                print(f"[gbdt] tree {t_idx:4d}  "
                      f"train_loss={train_loss:.6f}  "
                      f"({n_chunks[0]} chunks x {chunk_state['rows']} rows)")
            t_done = t_idx + 1
            if (recovery is not None and recovery.checkpoint_dir is not None
                    and (t_done - start) % recovery.checkpoint_every == 0):
                _save_round_checkpoint(t_done)
            if callback is not None:
                callback(t_idx, _as_model(trees, base_margin, config,
                                          missing_bin, F))
            t_idx = t_done
            if shutdown is not None and shutdown.requested:
                # the in-flight round is committed; persist the exact
                # resumable state, then exit with a typed status
                if (recovery is not None
                        and recovery.checkpoint_dir is not None
                        and (t_done - start) % recovery.checkpoint_every):
                    _save_round_checkpoint(t_done)
                partial = TrainResult(
                    model=_as_model(trees, base_margin, config,
                                    missing_bin, F),
                    history=history, step_times=step_times,
                    stats={**_stats(), "interrupted": True})
                raise TrainingInterrupted(
                    f"shutdown ({shutdown.signal_name}) after round "
                    f"{t_done - 1}", rounds_done=len(trees),
                    signal_name=shutdown.signal_name,
                    checkpoint_dir=(recovery.checkpoint_dir
                                    if recovery is not None else None),
                    result=partial)
            if stop_early:
                break

        return TrainResult(
            model=_as_model(trees, base_margin, config, missing_bin, F),
            history=history, step_times=step_times, stats=_stats())
    finally:
        # parity with PrefetchIterator: a fit never leaks the retry
        # wrapper's watchdog thread or its open shard handles, no matter
        # how it exits
        if isinstance(source, RetryingSource):
            source.close()
