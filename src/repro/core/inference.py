"""Batch inference extensions (paper §III-D) and the serving engine.

* ``predict_margin_cached`` — the compile-once predict engine: an
  lru-cached jitted step keyed on (plan, depth, K, missing bin) with
  power-of-two row- and tree-count padding buckets, so varying request
  batch sizes and checkpoint-resumed ensembles reuse ONE compiled
  executable per bucket instead of retracing per request.  Padding never
  changes results: padded rows are sliced off and padded trees are
  zero-leaf pass-throughs.
* ``sharded_predict`` — "the case of too many trees ... can be addressed
  by distributing the trees to multiple Booster chips (in a simple
  round-robin manner)": trees shard over the "model" mesh axis, records
  over the data axes; each shard runs its resident trees over its record
  block and one psum combines the (n,) ensemble sum — or the (n, K)
  per-class margins — tree-parallel x record-parallel, exactly the
  paper's multi-chip scheme.
* ``feature_importance`` — gain / cover / split-count importances from the
  fixed-shape tree arrays (production-model introspection).
* ``GBDTPipeline`` — binner + model bundle: predicts raw (unbinned,
  NaN-carrying) feature matrices through the device-resident binned
  transform + the cached engine, and round-trips through the checkpoint
  layer.
"""
from __future__ import annotations

import dataclasses
import threading
import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.api.plan import ExecutionPlan
from repro.core.binning import Binner, BinnedDataset, PackedCodes
from repro.core.gbdt import GBDTModel
from repro.kernels import ops
from repro.kernels.ref import TreeArrays
from repro.launch.mesh import data_axes


# --------------------------------------------------------------------------
# the compile-once predict engine (shape-bucketed jit cache)
# --------------------------------------------------------------------------
ROW_BUCKET_FLOOR = 128      # smallest row-padding bucket (pow2 above this)


def bucket_pow2(x: int, floor: int = 1) -> int:
    """The next power of two >= max(x, floor) — the row pad bucket."""
    return max(floor, 1 << max(0, int(x) - 1).bit_length())


def bucket_trees(T: int) -> int:
    """Tree-count pad bucket: the next multiple of 1/16th of T's power
    of two.  Unlike the row bucket, padded TREES cost real walk work on
    every request (a pass-through tree still walks), so a full pow2
    bucket would tax a fixed 513-tree ensemble ~2x forever; this
    granule caps the padding overhead at T/8 (12.5%) while a
    checkpoint-resumed, still-growing ensemble retraces at most 16
    times per doubling instead of every round."""
    g = max(1, bucket_pow2(T) // 16)
    return -(-int(T) // g) * g


def _inference_plan_key(plan: ExecutionPlan) -> ExecutionPlan:
    """Collapse a plan to the fields ensemble inference actually reads
    (traversal strategy, interpret mode, tree tile) so plans differing
    only in training-side knobs share one cached step."""
    return ExecutionPlan(traversal_strategy=plan.traversal_strategy,
                         interpret=plan.interpret,
                         trees_per_block=plan.trees_per_block).resolved()


def _build_predict_step(plan: ExecutionPlan, depth: int, n_classes: int,
                        missing_bin: int, trace_count):
    """One jitted predict step per (plan, depth, K, missing-bin) key.

    The jit's own shape cache then holds one executable per (row bucket,
    tree bucket, field count) — ``trace_count[0]`` counts exactly those
    compilations, which is what the serving loop asserts on.  The output
    accumulator arrives pre-filled with the base margin and is donated
    where the backend supports aliasing (TPU/GPU), so the margin add
    updates it in place.
    """
    def impl(out, codes, trees):
        trace_count[0] += 1                # trace-time side effect only
        m = ops.predict_ensemble(trees, codes, missing_bin=missing_bin,
                                 depth=depth, plan=plan,
                                 n_classes=n_classes)
        return out + m

    donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
    return jax.jit(impl, donate_argnums=donate)


class PredictCache:
    """A namespace of compiled predict steps (the serving jit cache).

    Each instance holds its own ``(plan, depth, K, missing-bin) -> jitted
    step`` table plus hit/miss/trace counters, so multi-tenant serving can
    key compiled executables *per model name*: two resident models never
    evict each other's steps, a hot-swapped model version inherits its
    predecessor's executables (zero retraces when the shape buckets
    match — trees are traced arguments, not compile-time constants), and
    ``ModelRegistry.unpublish`` drops exactly one model's compilations.

    The module-level default instance backs :func:`predict_margin_cached`
    when no ``cache=`` is passed (the single-model path), with
    :func:`predict_cache_stats` / :func:`predict_cache_clear` as its
    process-wide observability handles.  Thread-safe: serving worker
    threads and off-hot-path warmup may use one instance concurrently.
    """

    def __init__(self):
        self._steps = {}
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        self._traces = [0]          # shared with the jit closures

    def step(self, plan: ExecutionPlan, depth: int, n_classes: int,
             missing_bin: int):
        key = (plan, depth, n_classes, missing_bin)
        with self._lock:
            fn = self._steps.get(key)
            if fn is not None:
                self._hits += 1
                return fn
            self._misses += 1
        fn = _build_predict_step(plan, depth, n_classes, missing_bin,
                                 self._traces)
        with self._lock:
            # two threads may race to build the same key; keep the first
            return self._steps.setdefault(key, fn)

    def stats(self) -> Dict[str, int]:
        """``entries`` distinct (plan, depth, K) steps, ``traces`` total
        XLA compilations across all shape buckets (the serving loop's
        retrace counter)."""
        with self._lock:
            return {"entries": len(self._steps), "hits": self._hits,
                    "misses": self._misses, "traces": self._traces[0]}

    def clear(self) -> None:
        with self._lock:
            self._steps.clear()
            self._hits = self._misses = 0
            self._traces[0] = 0


_DEFAULT_CACHE = PredictCache()


def _padded_trees(model: GBDTModel, n_total: int) -> TreeArrays:
    """``model.trees`` zero-padded to exactly ``n_total`` trees, cached on
    the model instance so repeated requests reuse the device arrays."""
    cache = model.__dict__.setdefault("_pad_tree_cache", {})
    trees = cache.get(n_total)
    if trees is None:
        cache[n_total] = trees = pad_trees(model, n_total).trees
    return trees


def predict_margin_cached(model: GBDTModel, codes, *,
                          plan: Optional[ExecutionPlan] = None,
                          n_rows: Optional[int] = None,
                          cache: Optional[PredictCache] = None) -> jax.Array:
    """Ensemble margins through the compile-once engine.

    ``codes`` (or a :class:`BinnedDataset`) is padded up to a power-of-two
    row bucket (>= ``ROW_BUCKET_FLOOR``) and the ensemble up to its
    :func:`bucket_trees` bucket, so a serving stream of varying batch
    sizes (and a checkpoint-resumed, still-growing tree count) compiles
    once per bucket and never again.  Bucketing is invisible in the
    results: padded rows are sliced off before returning and padded
    trees output exactly 0.  ``n_rows`` marks the real row count when
    the caller already padded.  ``cache`` selects the step namespace
    (multi-tenant serving keys one :class:`PredictCache` per model name);
    ``None`` uses the process-wide default.
    """
    cache = cache if cache is not None else _DEFAULT_CACHE
    plan = _inference_plan_key(
        (plan if plan is not None else ExecutionPlan()).resolved())
    codes = codes.codes if isinstance(codes, BinnedDataset) else codes
    if isinstance(codes, PackedCodes):
        codes = codes.unpack()     # row buckets key on the uint8 layout
    codes = jnp.asarray(codes)
    n = int(codes.shape[0]) if n_rows is None else int(n_rows)
    row_bucket = bucket_pow2(int(codes.shape[0]), ROW_BUCKET_FLOOR)
    if int(codes.shape[0]) != row_bucket:
        codes = jnp.pad(codes, ((0, row_bucket - codes.shape[0]), (0, 0)))
    K = model.n_classes
    trees = _padded_trees(model, bucket_trees(model.n_trees))
    step = cache.step(plan, model.max_depth, K, model.missing_bin)
    base = jnp.asarray(model.base_margin, jnp.float32)
    out0 = (jnp.full((row_bucket,), base, jnp.float32) if K == 1
            else jnp.zeros((row_bucket, K), jnp.float32) + base)
    return step(out0, codes, trees)[:n]


def predict_cache_stats(cache: Optional[PredictCache] = None
                        ) -> Dict[str, int]:
    """Observability for a predict cache (the process-wide default when
    ``cache`` is None) — see :meth:`PredictCache.stats`."""
    return (cache if cache is not None else _DEFAULT_CACHE).stats()


def predict_cache_clear(cache: Optional[PredictCache] = None) -> None:
    (cache if cache is not None else _DEFAULT_CACHE).clear()


def sharded_predict(mesh: Mesh, model: GBDTModel, codes, *,
                    plan: Optional[ExecutionPlan] = None) -> jax.Array:
    """Tree-parallel x record-parallel ensemble inference on ``mesh``.

    Requires n_trees % mesh"model" == 0, and for multi-class ensembles a
    per-shard tree count that is a multiple of K so the round-major
    class routing survives contiguous sharding (pad the ensemble with
    zero-value trees via ``pad_trees(model, mesh_model * K)`` otherwise).
    Returns margins (n,), or (n, K) when ``model.n_classes > 1`` — each
    shard walks its resident trees and one psum combines the per-class
    columns.  ``plan`` selects the local traversal substrate (its own
    ``mesh`` field is ignored here — this IS the mesh dispatch).
    """
    da = data_axes(mesh)
    m = mesh.shape["model"]
    T = model.n_trees
    K = getattr(model, "n_classes", 1)
    if T % m:
        raise ValueError(f"{T} trees do not divide the model axis ({m}); "
                         "use pad_trees() first")
    if K > 1 and (T // m) % K:
        raise ValueError(
            f"{T} trees over {m} shards leave {T // m} trees per shard, "
            f"not a multiple of n_classes={K}; use pad_trees(model, "
            f"{m * K}) so round-major class routing survives sharding")
    if plan is None:
        plan = ExecutionPlan(traversal_strategy="reference")
    plan = plan.replace(mesh=None).resolved()

    def local(codes_l, *tree_leaves):
        trees_l = TreeArrays(*tree_leaves)       # (T/m, ...) local trees
        out = ops.predict_ensemble(trees_l, codes_l,
                                   missing_bin=model.missing_bin,
                                   depth=model.max_depth, plan=plan,
                                   n_classes=K)
        # paper §III-D: combine the per-chip tree outputs
        return jax.lax.psum(out, "model")

    # replicated per-shard zeros inside predict_ensemble are unvarying;
    # skip the static varying-axes check (the psum makes the output
    # well-defined)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(da, None),) + tuple(P("model") for _ in range(5)),
        out_specs=P(da, None) if K > 1 else P(da), check_vma=False)
    out = fn(codes, *model.trees)
    if K > 1:
        return out + jnp.asarray(model.base_margin, jnp.float32)
    return out + model.base_margin


def pad_trees(model: GBDTModel, multiple: int) -> GBDTModel:
    """Append zero-output pass-through trees so n_trees divides a mesh axis."""
    T = model.n_trees
    pad = -T % multiple
    if pad == 0:
        return model
    t = model.trees

    def pad0(a):
        return jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)

    padded = TreeArrays(
        feature=jnp.concatenate(
            [t.feature, jnp.full((pad,) + t.feature.shape[1:], -1,
                                 t.feature.dtype)]),
        threshold=pad0(t.threshold), is_cat=pad0(t.is_cat),
        default_left=pad0(t.default_left), leaf_value=pad0(t.leaf_value))
    return dataclasses.replace(model, trees=padded)


def feature_importance(model: GBDTModel, kind: str = "gain"
                       ) -> np.ndarray:
    """Per-field importance over the ensemble.

    kind: "split" (split counts), "gain" (sum of leaf-weight variance
    proxy per split — exact gains are not stored in the compact arrays,
    so subtree leaf-value spread stands in), or "cover" (uniform count
    weighting by subtree width).
    """
    feats = np.asarray(model.trees.feature)        # (T, n_int)
    leaves = np.asarray(model.trees.leaf_value, np.float64)  # (T, n_leaf)
    F = model.n_fields
    imp = np.zeros((F,), np.float64)
    T = feats.shape[0]
    depth = model.max_depth
    if kind == "split":
        valid = feats >= 0
        np.add.at(imp, feats[valid], 1.0)
    else:
        # vectorized per level: the heap positions at ``level`` cover the
        # bottom row in contiguous runs of reps = 2**(depth - level) slots,
        # so one reshape turns the subtree-leaf variance into a segment op
        for level in range(depth):
            nn = 2 ** level
            reps = 2 ** (depth - level)
            f_lvl = feats[:, nn - 1:2 * nn - 1]                # (T, nn)
            var = leaves.reshape(T, nn, reps).var(axis=2)      # (T, nn)
            w = float(reps) if kind == "cover" else 1.0
            valid = f_lvl >= 0
            np.add.at(imp, f_lvl[valid], w * var[valid])
    s = imp.sum()
    return imp / s if s > 0 else imp


@dataclasses.dataclass
class GBDTPipeline:
    """Binner + model bundle: raw float/NaN matrices in, predictions out.

    ``predict``/``predict_margin`` are the serving path: the raw batch is
    row-padded to its power-of-two bucket on the host, binned ON DEVICE
    (``Binner.transform_codes_device`` — no per-request numpy round-trip
    and no redundant column-major copy), and dispatched through the
    compile-once :func:`predict_margin_cached` engine.
    """

    binner: Binner
    model: GBDTModel

    def predict_margin(self, X: np.ndarray, *,
                       plan: Optional[ExecutionPlan] = None,
                       mode: str = "cached",
                       cache: Optional[PredictCache] = None) -> jax.Array:
        """Raw margins for a raw feature matrix.

        ``mode="cached"`` (the serving default) row-pads to the
        power-of-two bucket and dispatches through the compile-once
        engine; ``mode="direct"`` bins and walks the exact request shape
        (one-off calls that should not populate a jit cache).  ``cache``
        selects the step namespace for the cached mode.
        """
        if mode not in ("cached", "direct"):
            raise ValueError(f"unknown predict mode {mode!r}; choose "
                             "'cached' or 'direct'")
        X = np.asarray(X, dtype=np.float32)
        if mode == "direct":
            codes = self.binner.transform_codes_device(X)
            return self.model.predict_margin(codes, plan=plan)
        n = X.shape[0]
        row_bucket = bucket_pow2(n, ROW_BUCKET_FLOOR)
        if row_bucket != n:
            # zero-filled (not NaN) pad rows: they bin to real codes and
            # walk the trees, but are sliced off before returning
            X = np.pad(X, ((0, row_bucket - n), (0, 0)))
        codes = self.binner.transform_codes_device(X)
        return predict_margin_cached(self.model, codes, plan=plan,
                                     n_rows=n, cache=cache)

    def predict(self, X: np.ndarray, strategy: Optional[str] = None, *,
                plan: Optional[ExecutionPlan] = None,
                mode: str = "cached",
                cache: Optional[PredictCache] = None) -> jax.Array:
        base = plan if plan is not None else ExecutionPlan()
        if strategy is not None and strategy != "auto":
            warnings.warn(
                "legacy strategy-string kwargs are deprecated; pass "
                "plan=ExecutionPlan(traversal_strategy=...) instead",
                DeprecationWarning, stacklevel=2)
            base = base.replace(traversal_strategy=strategy)
        return self.model.loss.transform(
            self.predict_margin(X, plan=base, mode=mode, cache=cache))

    def to_state(self) -> Dict:
        return {
            "model": self.model.to_state(),
            "binner": {
                "max_bins": self.binner.max_bins,
                "categorical": sorted(self.binner.categorical_fields),
                "edges": self.binner._edges,
                "is_cat": self.binner._is_cat,
                "n_value_bins": self.binner._n_value_bins,
            },
        }

    @classmethod
    def from_state(cls, state: Dict) -> "GBDTPipeline":
        b = Binner(int(state["binner"]["max_bins"]),
                   [int(c) for c in np.asarray(
                       state["binner"]["categorical"]).ravel()])
        b._edges = np.asarray(state["binner"]["edges"])
        b._is_cat = np.asarray(state["binner"]["is_cat"])
        b._n_value_bins = np.asarray(state["binner"]["n_value_bins"])
        return cls(binner=b, model=GBDTModel.from_state(state["model"]))
