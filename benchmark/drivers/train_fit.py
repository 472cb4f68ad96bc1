"""Training through ``Estimator.fit``: a ``FeatureSet`` of seeded token ids,
shuffled, through the live ``PrefetchLoader``; the measured window is one
``fit`` call that a time-based ``end_trigger`` ends at the first epoch
boundary after ``--seconds`` (``fit`` looks at its trigger between epochs, so
an epoch is kept to a few steps).

End-to-end metric: ``train_tokens_per_s``, the tokens of the optimizer steps
``fit`` completed (``zoo_train_steps_total``) over the wall time of the call,
which ends in ``block_until_ready`` on the train state.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import harness
from benchmark import traffic_gen as traffic

# the first step's loss (bf16 compute) against the float32 reference on the
# same parameters and batch. At initialisation both are ln(vocab) plus a
# little, and bf16 rounding of the logits averages out over thousands of
# tokens: the v5e measured under 2e-3 (PR 22). Wrong data, labels shifted the
# wrong way or a broken head move the loss by tenths.
LOSS_ABS_TOL = 0.02


def _elapsed_trigger(seconds: float):
    from analytics_zoo_tpu.common.triggers import Trigger

    class Elapsed(Trigger):
        """True once ``seconds`` have passed since it was first asked."""

        def __init__(self):
            self.t0 = None

        def __call__(self, state) -> bool:
            now = time.monotonic()
            if self.t0 is None:
                self.t0 = now
            return now - self.t0 >= seconds

    return Elapsed()


def lowered_collectives(est, batch) -> dict:
    """How often each collective appears in the lowered train step."""
    text = est.lower_train_step(batch).as_text()
    return {name: text.count(f'stablehlo.{name}"') for name in
            ("reduce_scatter", "all_gather", "all_reduce")}


def run(run: harness.Run) -> harness.Outcome:
    import jax

    from analytics_zoo_tpu.common import TrainConfig
    from analytics_zoo_tpu.common.triggers import MaxEpoch
    from analytics_zoo_tpu.data import FeatureSet
    from analytics_zoo_tpu.engine import Estimator

    mix, config = run.traffic, run.config
    ctx = harness.make_context(config)
    n_dev = ctx.mesh.devices.size
    model = harness.build_model(config)
    seq_len, batch = int(mix["seq_len"]), int(mix["global_batch"])
    n_seq = batch * int(mix["steps_per_epoch"])
    x, y = traffic.token_batches(mix, run.seed, n_seq, seq_len, model.vocab)
    first = (x[:batch], y[:batch])

    params = harness.make_params(model, run.seed)
    reference, kwargs = harness.reference_of(config)
    want_loss = reference.loss(params, *first, **kwargs)
    run.say("reference", loss=round(want_loss, 5))

    training = config["training"]
    est = Estimator(model, optimizer=harness.construct(training["optimizer"],
                                                       config),
                    loss=harness.named(training["loss"]), mesh=ctx.mesh,
                    config=TrainConfig(**training["TrainConfig"]))
    est.initial_weights = (params, {})
    # one step on the first batch: builds the state, compiles the step, and
    # its loss is the loss of the seeded parameters
    est.fit(FeatureSet.from_numpy(*first), batch_size=batch, epochs=1)
    got_loss = float(est.trainer_state.last_loss)
    est.initial_weights = None
    del params
    run.say("first-step", loss=round(got_loss, 5),
            executables_built=run.compiles.count)

    faults = []
    if not abs(got_loss - want_loss) <= LOSS_ABS_TOL:
        faults.append(f"first step's loss {got_loss} against the "
                      f"reference's {want_loss} (tolerance {LOSS_ABS_TOL})")
    placed = est._to_global(first)[0].addressable_shards
    if len({s.device for s in placed}) != n_dev:
        faults.append(f"batch on {len({s.device for s in placed})} devices "
                      f"of {n_dev}")
    wanted = mix.get("lowered_collectives")
    if wanted:
        found = lowered_collectives(est, first)
        run.say("lowered", **found)
        for name, n in wanted.items():
            if found.get(name) != n:
                faults.append(f"lowered step has {found.get(name)} {name}, "
                              f"not {n}")

    data = FeatureSet.from_numpy(x, y)
    # warm epochs over the real set, loader and shuffle included, past the
    # first log point (where fit reads the loss back for the first time)
    while est.trainer_state.iteration <= est.config.log_every_n_steps:
        est.fit(data, batch_size=batch,
                end_trigger=MaxEpoch(est.trainer_state.epoch + 1))

    c0, compiles0 = harness.counters(), run.compiles.count
    tracer = run.trace_window()
    t0 = time.monotonic()
    est.fit(data, batch_size=batch, end_trigger=_elapsed_trigger(run.seconds))
    t1 = time.monotonic()
    c1 = harness.counters()
    last_loss = float(est.trainer_state.last_loss)

    steps = int(c1["zoo_train_steps_total"] - c0["zoo_train_steps_total"])
    obs = {"window": (t0, t1), "counters0": c0, "counters1": c1,
           "compiles_in_window": run.compiles.count - compiles0}
    if tracer is not None:
        obs.update(tracer.finish())
    if obs["compiles_in_window"]:
        faults.append(f"{obs['compiles_in_window']} executables were built "
                      f"inside the window")
    finite = math.isfinite(last_loss)
    if not finite:
        faults.append(f"loss after the window is {last_loss}")
    run.say("window", steps=steps, seconds=round(t1 - t0, 3),
            loss=f"{got_loss:.4f}->{last_loss:.4f}")
    obs["faults"] = faults
    return harness.Outcome(
        correct=not faults, attempted=steps,
        # a non-finite loss never recovers under Adam: every step then failed
        failed=0 if finite else steps,
        end_to_end={"train_tokens_per_s": steps * batch * seq_len / (t1 - t0),
                    "setup_s": t0 - run.t_process_start},
        observations=obs, notes=faults,
        checks={"first_step_loss_diff": [abs(got_loss - want_loss),
                                         LOSS_ABS_TOL]})
