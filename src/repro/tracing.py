"""Spans and scopes that put the boosting round on the profiler's clock.

Every span name, device scope and ``step_times`` key that the round loops
write and the benchmark's readers read is defined here, once.  Nothing
here is a tracing system of its own; it is JAX's:

* a host span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``
  (a few microseconds when no profiler runs);
* a device scope is a ``jax.named_scope``: traced ops carry it in their
  metadata's name stack, which a TPU trace keeps as the ``tf_op`` stat of
  each op's event metadata.  A scope names only ops traced inside a
  ``jit`` under it; an op dispatched eagerly from the host compiles alone
  and carries none.

A span given ``times`` and ``key`` also adds its seconds to
``times[key]``: ``step_times`` is the counter half of the same span.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional

import jax

PREFIX = "repro."

# host spans, each traced as PREFIX + name
ROUND = "round"                  # one boosting round; step_num = its index
GROW = "grow"                    # steps ①–④, to the tree's sync
GRADIENTS = "gradients"          # inside grow: g, h and the round's filters
MARGIN_UPDATE = "margin_update"  # step ⑤, to the margins' sync
LOSS = "loss"                    # the training loss, fetched
EVAL = "eval"                    # the eval set's margins and loss, fetched
DISPATCH = "dispatch"            # the depthwise round's one jitted call
COMMIT = "commit"                # early stop, logging, sentinel, callback,
#                                  the shutdown question
SYNC = "sync"                    # the host blocked on the device; meta
#                                  ``what``: tree, margins, loss, eval,
#                                  sentinel or snapshot

# device scopes (the name stack of the ops traced under them)
STEP1 = "step1_histogram"        # per level: step1_histogram/level<l>
STEP2 = "step2_split"            # per level
STEP3 = "step3_partition"        # per level
STEP4 = "step4_leaves"
STEP5 = "step5_traversal"

# step_times keys: seconds summed over the fit's rounds
BINNING_SPLIT = "binning_split"  # the grow span
TRAVERSAL = "traversal"          # the margin_update span
OTHER = "other"                  # the loss and eval spans
FUSED_ROUNDS = "fused_rounds"    # the depthwise loop, start to end
SYNC_WAIT = "sync_wait"          # the sync spans: a sub-total, not a phase
HOST_LOOP_KEYS = (BINNING_SPLIT, TRAVERSAL, OTHER)


@contextlib.contextmanager
def span(name: str, times: Optional[Dict[str, float]] = None,
         key: Optional[str] = None, **meta):
    """A host span ``repro.<name>``; with ``times`` and ``key`` its
    seconds are added to ``times[key]``."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(PREFIX + name, **meta):
        yield
    if times is not None:
        times[key] += time.perf_counter() - t0


def round_span(i: int):
    """Round ``i``: one name for every round, the index as metadata."""
    return jax.profiler.StepTraceAnnotation(PREFIX + ROUND, step_num=i)


def sync(what: str, times: Dict[str, float]):
    """Wraps a call that blocks the host on the device (a
    ``block_until_ready``, ``float``, ``bool``, ``device_get`` or
    ``np.asarray`` of a device array); its seconds go to ``sync_wait``."""
    return span(SYNC, times, SYNC_WAIT, what=what)


def scope(name: str, level: Optional[int] = None):
    """The device scope ``name``, or ``name/level<l>`` for one level."""
    return jax.named_scope(name if level is None else f"{name}/level{level}")
