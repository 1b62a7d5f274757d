"""Inputs made on the device from ``--seed``: tabular records and a served
ensemble.

The record recipe is ``repro.data.synthetic.make_tabular`` (numeric fields
N(0, 1), categorical ids uniform over ``n_cats``, a planted shallow-tree
target over six fields plus noise, labels drawn from its sigmoid, NaN for
missing values), rewritten as one jitted call so that ten million records
take a second on the chip instead of a minute of host numpy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PLANTED_FIELDS = 6          # make_tabular: min(F, 6) fields carry the target


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size: ``PRNGKey`` keeps only the low
    32 bits, so the high bits are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
        hi >>= 32
    return key


@functools.partial(jax.jit, static_argnames=("n", "n_numeric",
                                             "n_categorical", "n_cats",
                                             "missing_rate"))
def tabular(key, *, n: int, n_numeric: int, n_categorical: int,
            n_cats: int, missing_rate: float):
    """(X float32 (n, F) with NaN for missing, y float32 (n,) in {0, 1})."""
    F = n_numeric + n_categorical
    k = jax.random.split(key, 10)
    num = jax.random.normal(k[0], (n, n_numeric), jnp.float32)
    cat = jax.random.randint(k[1], (n, n_categorical), 0, max(n_cats, 1))
    X = jnp.concatenate([num, cat.astype(jnp.float32)], axis=1)
    is_cat = jnp.arange(F) >= n_numeric

    picks = jax.random.permutation(k[2], F)[:min(F, PLANTED_FIELDS)]
    cat_vals = jax.random.normal(k[3], (picks.shape[0], max(n_cats, 1)))
    thr = jax.random.normal(k[4], (picks.shape[0],))
    lo_hi = jax.random.normal(k[5], (picks.shape[0], 2))
    margin = jnp.zeros((n,), jnp.float32)
    for i in range(picks.shape[0]):
        col = jnp.take(X, picks[i], axis=1)
        cat_term = cat_vals[i][jnp.clip(col, 0, n_cats - 1).astype(jnp.int32)]
        num_term = jnp.where(col > thr[i], lo_hi[i, 0], lo_hi[i, 1])
        margin += jnp.where(is_cat[picks[i]], cat_term, num_term)
    first = jnp.take(X, picks[0], axis=1)
    last = jnp.take(X, picks[-1], axis=1)
    margin += 0.5 * jnp.sin(first * 2.0) * (last > 0)
    margin += 0.1 * jax.random.normal(k[6], (n,))
    y = (jax.random.uniform(k[7], (n,)) < jax.nn.sigmoid(margin))
    if missing_rate > 0:
        miss = jax.random.uniform(k[8], (n, F)) < missing_rate
        X = jnp.where(miss, jnp.nan, X)
    return X, y.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_trees", "depth"))
def ensemble(key, is_cat_field, n_value_bins, *, n_trees: int, depth: int,
             leaf_scale: float):
    """Random full-depth trees in ``TreeArrays`` field order (feature,
    threshold, is_cat, default_left, leaf_value).  Every internal node
    splits; a numeric split ``code <= t`` draws t from the field's value
    bins but its last, a categorical split ``code == c`` draws a category
    the binner knows."""
    n_int = 2 ** depth - 1
    k = jax.random.split(key, 4)
    F = is_cat_field.shape[0]
    feature = jax.random.randint(k[0], (n_trees, n_int), 0, F, jnp.int32)
    nvb = n_value_bins[feature]
    cat = is_cat_field[feature]
    u = jax.random.uniform(k[1], (n_trees, n_int))
    span = jnp.where(cat, nvb, jnp.maximum(nvb - 1, 1))
    threshold = jnp.minimum((u * span).astype(jnp.int32), span - 1)
    default_left = jax.random.randint(k[2], (n_trees, n_int), 0, 2,
                                      jnp.int32)
    leaf = leaf_scale * jax.random.normal(k[3], (n_trees, n_int + 1),
                                          jnp.float32)
    return (feature, threshold, cat.astype(jnp.int32), default_left, leaf)
