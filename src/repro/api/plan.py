"""ExecutionPlan — the single object that decides *where* each GBDT step runs.

Every accelerated step (histogram ①, partition ③, traversal/inference ⑤)
used to take its own ``strategy=`` / ``interpret=`` kwargs, and callers had
to thread three strings plus an interpret flag through ``GBDTConfig``,
``train``, the pipeline and the kernels.  An ``ExecutionPlan`` centralizes
that selection: build one (or let ``ExecutionPlan.auto()`` probe the
backend once), pass it down, and every dispatch layer reads from it.

A plan is a frozen, hashable dataclass, so it can ride through ``jax.jit``
as a static argument — strategy choices are compile-time decisions.

Strategy fields accept ``"auto"``; ``resolved()`` replaces every ``"auto"``
(and a ``None`` interpret flag) with the backend default, so kernels only
ever see concrete choices.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax

HIST_STRATEGIES = ("scatter", "scatter_private", "sort", "onehot",
                   "pallas_grouped", "pallas_packed")
PARTITION_STRATEGIES = ("reference", "pallas")
TRAVERSAL_STRATEGIES = ("reference", "scan", "pallas")


@functools.lru_cache(maxsize=None)
def _backend() -> str:
    """Probe the JAX backend exactly once per process."""
    return jax.default_backend()


def _on_tpu() -> bool:
    return _backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Kernel/strategy/interpret/mesh selection for every GBDT step.

    Fields
    ------
    hist_strategy:       step ① — one of ``HIST_STRATEGIES`` or ``"auto"``
    partition_strategy:  step ③ — ``"reference"`` | ``"pallas"`` | ``"auto"``
    traversal_strategy:  step ⑤ / batch inference — ``"reference"`` (the
                         tree-batched level walk: every tree advances one
                         depth level per pass over the codes),
                         ``"scan"`` (legacy one-tree-at-a-time lax.scan —
                         kept as the baseline the benchmarks compare
                         against), ``"pallas"`` (tree-blocked compare-walk
                         kernel), or ``"auto"``
    interpret:           run Pallas kernels in interpret mode (None = auto:
                         interpret everywhere except a real TPU; True is
                         refused on a TPU, where the kernels compile)
    records_per_block:   Pallas histogram grid — records per kernel block
    fields_per_block:    Pallas histogram grid — fields per kernel block
    trees_per_block:     Pallas batch inference (§III-D) — tree tables
                         resident per grid step; each record block fetched
                         into VMEM is amortized across this many trees
                         (the ensemble is zero-padded to a multiple)
    host_offload_split:  run step ② split selection on host (paper's offload)
    hist_subtraction:    step ① sibling subtraction in the level-wise
                         growers — at each level > 0 only the *smaller*
                         child of every split parent is binned explicitly
                         and the sibling histogram is derived as
                         ``parent − smaller`` (paper §II-A, "without any
                         explicit binning at the other child").  ``None``
                         resolves to ``False``: the derived sibling is a
                         float-reassociated value (documented tolerance,
                         see ``docs/api.md``), so the direct pass stays
                         the default
    chunk_bytes:         out-of-core training budget — caps the bytes of
                         binned records resident on device at once; when
                         set, ``fit(data=...)`` streams chunk-sized
                         histogram/partition passes instead of
                         materializing the matrix (None = in-memory)
    packed_codes:        store/stream bin codes 4-bit packed (two per byte,
                         paper §III-B's compressed redundant representation).
                         ``None`` = auto: pack whenever the dataset's
                         ``n_bins <= 16``; ``True`` forces packing (errors
                         above 16 bins); ``False`` forces plain uint8.
                         Affects the resident-bytes model of
                         ``chunk_rows()`` and the layout the streaming
                         trainer writes/ships — results are bit-equal
                         either way
    mesh:                optional ``jax.sharding.Mesh``; when set, ensemble
                         inference shards trees over the ``"model"`` axis and
                         records over the data axes (paper §III-D), and
                         ``train``/``fit`` route through the data-parallel
                         distributed trainer (paper §III-B — per-shard
                         histograms + one psum per level)
    data_axes:           mesh axes carrying *records* during distributed
                         training; ``None`` resolves to every mesh axis
                         except ``"model"`` (``launch.mesh.data_axes``).
                         Only meaningful together with ``mesh``
    """

    hist_strategy: str = "auto"
    partition_strategy: str = "auto"
    traversal_strategy: str = "auto"
    interpret: Optional[bool] = None
    records_per_block: int = 512
    fields_per_block: int = 8
    trees_per_block: int = 8
    host_offload_split: bool = False
    hist_subtraction: Optional[bool] = None
    packed_codes: Optional[bool] = None
    chunk_bytes: Optional[int] = None
    mesh: Optional[jax.sharding.Mesh] = None
    data_axes: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        if self.data_axes is not None:
            # normalize lists so plans stay hashable jit keys
            object.__setattr__(self, "data_axes",
                               tuple(str(a) for a in self.data_axes))
            if self.mesh is None:
                raise ValueError("data_axes only applies together with a "
                                 "mesh (the distributed-training record "
                                 "axes)")
            missing = set(self.data_axes) - set(self.mesh.axis_names)
            if missing:
                raise ValueError(
                    f"data_axes {sorted(missing)} not present on the mesh "
                    f"(axes: {self.mesh.axis_names})")
        if self.chunk_bytes is not None and self.chunk_bytes <= 0:
            raise ValueError("chunk_bytes must be positive (or None for "
                             "in-memory training)")
        if self.trees_per_block < 1:
            raise ValueError("trees_per_block must be >= 1")
        if self.hist_strategy not in HIST_STRATEGIES + ("auto",):
            raise ValueError(
                f"unknown histogram strategy {self.hist_strategy!r}; "
                f"choose from {HIST_STRATEGIES + ('auto',)}")
        if self.partition_strategy not in PARTITION_STRATEGIES + ("auto",):
            raise ValueError(
                f"unknown partition strategy {self.partition_strategy!r}")
        if self.traversal_strategy not in TRAVERSAL_STRATEGIES + ("auto",):
            raise ValueError(
                f"unknown traversal strategy {self.traversal_strategy!r}")

    # -- construction ------------------------------------------------------
    @classmethod
    def auto(cls, mesh: Optional[jax.sharding.Mesh] = None,
             **overrides) -> "ExecutionPlan":
        """Backend-probed plan: Pallas kernels on TPU, software paths (with
        Pallas interpret-mode validation available) everywhere else."""
        return cls(mesh=mesh, **overrides).resolved()

    @classmethod
    def from_config(cls, config=None,
                    mesh: Optional[jax.sharding.Mesh] = None, *,
                    base: Optional["ExecutionPlan"] = None,
                    distributed: bool = False,
                    hist_strategy: Optional[str] = None
                    ) -> "ExecutionPlan":
        """Lift legacy config-level strategy selections into one plan.

        Two spellings fold here (both deprecated at their call sites,
        kept for one release):

        * ``from_config(config)`` — lift the per-step strategy strings
          off a ``GBDTConfig``.
        * ``from_config(base=plan, hist_strategy=..., distributed=True)``
          — the distributed growers' historical defaults (previously
          ``distributed/sharding._legacy_distributed_plan``): no plan
          means scatter histograms regardless of backend; an explicit
          loose ``hist_strategy`` overrides the plan's field; and
          ``distributed=True`` resolves the partition step to the
          reference kernel: those field-sharded growers route records on
          globally sharded arrays, where a Pallas kernel cannot be
          partitioned (``train_distributed`` runs it per shard).

        The result is always :meth:`resolved`.
        """
        if config is not None:
            if base is not None or hist_strategy is not None:
                raise ValueError("pass either config or base/hist_strategy,"
                                 " not both")
            base = cls(hist_strategy=config.hist_strategy,
                       partition_strategy=config.partition_strategy,
                       traversal_strategy=config.traversal_strategy,
                       host_offload_split=config.host_offload_split,
                       mesh=mesh)
        elif base is None:
            base = (cls(hist_strategy=hist_strategy or "scatter", mesh=mesh)
                    if distributed else cls(mesh=mesh))
        plan = resolve_plan(base, hist_strategy=hist_strategy)
        if distributed:
            plan = plan.replace(partition_strategy="reference")
        return plan

    def resolved(self) -> "ExecutionPlan":
        """Replace every ``"auto"`` / ``None`` with the backend default.

        On TPU the ``"auto"`` defaults elect the Pallas kernels, compiled
        (``interpret=False``); elsewhere the jnp paths, with the kernels
        available in interpret mode for validation.  Nothing is probed:
        a kernel that fails to compile or launch raises at its dispatch.
        ``interpret=True`` on a TPU is refused — it would run the kernels
        as host Python while the plan claims the chip.
        """
        tpu = _on_tpu()
        if tpu and self.interpret:
            raise ValueError("interpret=True is refused on a TPU backend: "
                             "the Pallas kernels compile there "
                             "(leave interpret=None or pass False)")
        kw = {}
        if self.hist_strategy == "auto":
            kw["hist_strategy"] = "pallas_grouped" if tpu else "scatter"
        if self.partition_strategy == "auto":
            kw["partition_strategy"] = "pallas" if tpu else "reference"
        if self.traversal_strategy == "auto":
            kw["traversal_strategy"] = "pallas" if tpu else "reference"
        if self.interpret is None:
            kw["interpret"] = not tpu
        if self.hist_subtraction is None:
            kw["hist_subtraction"] = False
        return dataclasses.replace(self, **kw) if kw else self

    def replace(self, **changes) -> "ExecutionPlan":
        return dataclasses.replace(self, **changes)

    # -- out-of-core chunking ----------------------------------------------
    DEFAULT_CHUNK_BYTES = 1 << 26          # 64 MiB of resident chunk state

    def chunk_rows(self, n_fields: int, n_classes: int = 1) -> int:
        """Rows per streamed chunk under the ``chunk_bytes`` budget.

        Per-row resident footprint during a chunked pass: the code row
        plus its column-major transpose (2F bytes unpacked; F bytes when
        ``packed_codes`` halves both copies to a nibble each) and the
        per-class float32 g/h/node-id triple (12K bytes).
        """
        budget = self.chunk_bytes or self.DEFAULT_CHUNK_BYTES
        code_bytes = (1 if self.packed_codes else 2) * max(n_fields, 1)
        per_row = code_bytes + 12 * max(n_classes, 1)
        return max(256, budget // per_row)

    def without_chunking(self) -> "ExecutionPlan":
        """Drop ``chunk_bytes`` so kernel-level jits (which take the plan
        as a static argument) don't recompile across chunk budgets."""
        if self.chunk_bytes is None:
            return self
        return dataclasses.replace(self, chunk_bytes=None)

    def describe(self) -> str:
        m = (f"mesh{dict(self.mesh.shape)}" if self.mesh is not None
             else "single-device")
        sub = "+sub" if self.hist_subtraction else ""
        if self.packed_codes is not None:
            sub += f", packed={self.packed_codes}"
        return (f"ExecutionPlan(hist={self.hist_strategy}{sub}, "
                f"partition={self.partition_strategy}, "
                f"traversal={self.traversal_strategy}, "
                f"interpret={self.interpret}, {m})")


def resolve_plan(plan: Optional[ExecutionPlan] = None,
                 **loose) -> ExecutionPlan:
    """Resolve a plan plus config-level loose kwargs into a concrete plan.

    ``loose`` entries that are ``None`` or ``"auto"`` are ignored; any other
    value overrides the plan field of the same name.  This is the lifting
    layer for config-level strategy strings (``GBDTConfig``'s legacy
    fields, ``distributed_histogram(strategy=...)``); the ``kernels.ops``
    entry points take ``plan=`` only.
    """
    loose = {k: v for k, v in loose.items()
             if v is not None and v != "auto"}
    base = plan if plan is not None else ExecutionPlan()
    if loose:
        base = dataclasses.replace(base, **loose)
    return base.resolved()
