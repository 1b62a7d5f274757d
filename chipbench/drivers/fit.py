"""The ``fit`` driver: boosting rounds through ``repro.core.gbdt.train``.

Set-up makes the records on the device from the seed, fits the program's
``Binner`` on a sample of them, bins every record on the device with
``Binner.transform_codes_device``, and runs a one-round fit at the cell's
shapes so that every program the rounds use is compiled (or loaded from
the persistent cache).  The window is one ``train`` call with room for
far more rounds than fit; ``train`` asks its ``shutdown`` object after
every committed round whether to stop, and the harness's answer turns
yes at the first round boundary after ``--seconds`` (and after the
rounds the comparison needs).  The reference then grows the same first
rounds from the same codes and labels, and the trees and losses are
compared; and it bins a sample of the raw records, made again from the
seed, with edges of its own from the binner's sample rows, and the codes
are compared.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from chipbench import data as data_mod
from chipbench.harness import Check, Outcome, Records, log, span
from chipbench.reference import binning_ref, fit_ref

MAX_ROUNDS = 100_000        # the window, not the round count, ends the fit


def _stopper(seconds: float, min_rounds: int, clock):
    from repro.resilience.shutdown import GracefulShutdown

    class WindowStop(GracefulShutdown):
        """``train``'s between-rounds question, answered by the clock; each
        question marks a round boundary, with a host span per round."""

        def __init__(self):
            super().__init__(signals=())
            self.t0 = None
            self.ends = []
            self.compiles = []
            self._round = None
            self._measured = span("measured")

        def start(self):
            self._measured.__enter__()
            self.t0 = time.perf_counter()
            self._open()

        def _open(self):
            self._round = span(f"round.{len(self.ends)}")
            self._round.__enter__()

        def close(self):
            if self._round is not None:
                self._round.__exit__(None, None, None)
                self._round = None

        def finish(self):
            self.close()
            if self._measured is not None:
                self._measured.__exit__(None, None, None)
                self._measured = None

        @property
        def requested(self) -> bool:
            now = time.perf_counter()
            self.close()
            self.ends.append(now)
            self.compiles.append(clock.compiles)
            if (len(self.ends) >= min_rounds
                    and now - self.t0 >= seconds):
                self.finish()
                self.request("window closed")
            else:
                self._open()
            return super().requested

    return WindowStop()


def _gbdt_config(model: dict, n_trees: int):
    from repro.core.gbdt import GBDTConfig
    return GBDTConfig(n_trees=n_trees, max_depth=model["max_depth"],
                      learning_rate=model["learning_rate"],
                      lambda_=model["lambda_"], gamma=model["gamma"],
                      min_child_weight=model["min_child_weight"],
                      objective=model["objective"])


def _records(config: dict, seed: int):
    """(X, y) of the seed, on the device."""
    ds = config["dataset"]
    return data_mod.tabular(data_mod.seed_key(seed), n=ds["records"],
                            n_numeric=ds["numeric_fields"],
                            n_categorical=ds["categorical_fields"],
                            n_cats=ds["categories"],
                            missing_rate=ds["missing_rate"])


def prepare(config: dict, traffic: dict, seed: int, clock, phases):
    """The records of the seed, binned: (binned dataset, codes, labels).
    ``codes`` is the (n, F) uint8 matrix the dataset was made from."""
    import jax
    from repro.core.binning import Binner, PackedCodes

    ds, model = config["dataset"], config["model"]
    n_num = ds["numeric_fields"]
    F = n_num + ds["categorical_fields"]
    with clock.phase("data", phases):
        X, y = _records(config, seed)
        jax.block_until_ready((X, y))
    with clock.phase("binning", phases):
        sample = np.asarray(X[:traffic["binner_sample_rows"]])
        binner = Binner(max_bins=model["max_bins"],
                        categorical_fields=list(range(n_num, F))).fit(sample)
        codes = binner.transform_codes_device(X)
        layout = binner.transform(sample[:1])    # the program's layout
        if isinstance(layout.codes, PackedCodes):
            data = dataclasses.replace(layout,
                                       codes=PackedCodes.pack(codes),
                                       codes_cm=PackedCodes.pack(codes.T))
        else:
            data = dataclasses.replace(layout, codes=codes,
                                       codes_cm=codes.T.copy())
        jax.block_until_ready((data.codes, data.codes_cm))
    return data, codes, y


def reference_rounds(config: dict, codes, y, rounds: int, **kw):
    """The reference's first ``rounds`` rounds on the same codes."""
    ds, model = config["dataset"], config["model"]
    F = ds["numeric_fields"] + ds["categorical_fields"]
    return fit_ref.fit_rounds(codes, y, rounds=rounds,
                              depth=model["max_depth"],
                              n_bins=model["max_bins"],
                              is_cat_field=np.arange(F) >= ds["numeric_fields"],
                              lambda_=model["lambda_"], gamma=model["gamma"],
                              min_child_weight=model["min_child_weight"],
                              learning_rate=model["learning_rate"], **kw)


def code_mismatch(config: dict, traffic: dict, seed: int, codes,
                  precision: str = "float32") -> int:
    """How many codes of a sample of records, drawn from the seed, differ
    between ``codes`` and the reference's binning of the raw records.  The
    records are made again from the seed; the reference fits its edges on
    the rows the program's binner was fitted on.  ``precision="bfloat16"``
    puts the reference in bfloat16 in the codes' place: the control."""
    ds, model = config["dataset"], config["model"]
    n = ds["records"]
    X, _ = _records(config, seed)
    rows = np.sort(np.random.default_rng(seed).choice(
        n, size=min(traffic["checked_records"], n), replace=False))
    sample = np.asarray(X[:traffic["binner_sample_rows"]])
    raw = np.asarray(X[rows])
    del X
    categorical = set(range(ds["numeric_fields"], raw.shape[1]))
    want = binning_ref.bin_rows(
        raw, binning_ref.fit_edges(sample, categorical, model["max_bins"]))
    if precision == "bfloat16":
        low = binning_ref.fit_edges(binning_ref.to_bf16(sample), categorical,
                                    model["max_bins"])
        got = binning_ref.bin_rows(binning_ref.to_bf16(raw), low)
    elif precision == "float32":
        got = np.asarray(codes[rows])
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return int(np.count_nonzero(got != want))


def run(ctx) -> Outcome:
    import jax
    from repro.core.gbdt import train
    from repro.resilience.errors import TrainingInterrupted

    cfg, traffic, limits = ctx.config, ctx.traffic, ctx.limits
    model = cfg["model"]
    compared = traffic["compared_rounds"]
    data, codes, y = prepare(cfg, traffic, ctx.seed, ctx.clock, ctx.phases)
    with ctx.clock.phase("warmup", ctx.phases):
        warm = train(_gbdt_config(model, 1), data, y, plan=ctx.plan)
        jax.block_until_ready(warm.model.trees)
        del warm

    stop = _stopper(ctx.seconds, compared, ctx.clock)
    c0 = ctx.clock.compiles
    with ctx.window():
        stop.start()
        try:
            result = train(_gbdt_config(model, MAX_ROUNDS), data, y,
                           plan=ctx.plan, shutdown=stop)
        except TrainingInterrupted as exc:
            result = exc.result
        finally:
            stop.finish()
    rounds = len(stop.ends)
    window_s = stop.ends[-1] - stop.t0
    in_window = stop.compiles[-1] - c0
    log(f"window: {rounds} rounds in {window_s:.6f} s, "
        f"{in_window} compiles inside it")
    peak = ctx.memory_peak()

    trees = result.model.trees
    prog_trees = [fit_ref.Tree(*[np.asarray(a[i]) for a in trees])
                  for i in range(compared)]
    prog_losses = [float(v) for v in result.history["train_loss"][:compared]]
    step_times = dict(result.step_times)
    del result, trees, data

    t_ref = time.perf_counter()
    got = {"code_mismatch": code_mismatch(cfg, traffic, ctx.seed, codes)}
    log(f"reference binning: {got['code_mismatch']} codes differ in "
        f"{time.perf_counter() - t_ref:.3f} s")
    t_ref = time.perf_counter()
    ref = reference_rounds(cfg, codes, y, compared)
    got.update(fit_ref.compare(prog_trees, prog_losses, ref))
    log(f"reference: {compared} rounds in {time.perf_counter() - t_ref:.3f} "
        f"s; {got['counts']}; split_shortfall {got['split_shortfall']!r} "
        f"(not compared)")
    checks = [Check(name, got[name], limits[name]) for name in
              ("code_mismatch", "light_splits", "leaf_gap", "loss_gap")]

    records = Records(cell=ctx.cell, config=cfg, traffic=traffic,
                      peaks=ctx.peaks,
                      fit={"rounds": rounds, "window_s": window_s,
                           "step_times": step_times,
                           "records": codes.shape[0],
                           "fields": codes.shape[1], "bins": model["max_bins"],
                           "depth": model["max_depth"],
                           "compiles_in_window": in_window})
    return Outcome(metrics={"fit_round_s": window_s / rounds},
                   checks=checks, attempted=rounds, failed=0,
                   memory_peak_bytes=peak, records=records)
