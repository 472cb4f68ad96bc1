"""``gen_open_loop`` for a configuration whose outputs do not read, against
the reference, what the GPT-2 block's read: the same rig, the same four
checks, the same controls, with the limits the cell's own file states.

The rig's limits of the three numbers read from outputs
(``serving_rig.SERVED_GAP_TOL``, ``LOGIT_REL_RMS_TOL``, ``LOGIT_MAX_ABS_TOL``)
were set between readings of the GPT-2 block on the v5e and of its float8
control (PR 34). They are properties of a configuration, not of the rig: how
far bfloat16 rounding moves a logit, and how often it parts the served token
from the reference's best, depends on the layers, the vocabulary and the
lengths served (PERF.md, section 6, PR 43). A cell that names this driver
states under ``limits`` in its ``workloads/<cell>.json``, for each number it
moves: the ``limit``, the ``program``'s largest reading over its seeds, the
smallest reading of the ``control`` a precision below (float8), and ``why``.
A limit lies between its two readings, with room on both sides. A number the
cell does not state keeps the rig's limit; ``narrow_operands`` is 0, an exact
comparison, for every configuration, and is what fails the int8 control.

Each run is a process of its own, so the limits are set on the rig's module
for this run and no other.

A traced run also says WHERE ``served_gap`` comes from (``gap_profile``, one
line a sampled request; its numbers refuse nothing): the widest gap in each
quarter of the served stream, so that an error that grows with the length
shows as one, and beside it the same reading of a witness, the reference
with every product's operands rounded to bfloat16 (``precision="bf16"``): if
the witness reads what the program reads, the gap is the stated precision's
and not the program's.
"""

from __future__ import annotations

import numpy as np

from benchmark import harness, serving_rig
from benchmark.drivers import gen_open_loop

_RIG_NAME = {"served_gap": "SERVED_GAP_TOL",
             "logit_rel_rms": "LOGIT_REL_RMS_TOL",
             "logit_max_abs": "LOGIT_MAX_ABS_TOL"}


def _gap_profile(rig, sample, params) -> None:
    """``gap_profile`` lines: per sampled request the widest ``served_gap``
    in each quarter of its served tokens, the program's and the bfloat16
    witness's (the witness's first token at each position, read as the
    rig's control is read)."""
    from benchmark import traffic_gen as traffic

    reference, kwargs = harness.reference_of(rig.run.config)
    for r in sample:
        tokens = np.asarray(r["tokens"], np.int32)
        seq = np.concatenate([traffic.prompt_tokens(
            rig.mix, r, rig.model.vocab), tokens[:-1]])
        pad = serving_rig.REFERENCE_PAD
        ids = np.zeros((1, min(-(-len(seq) // pad) * pad,
                               int(rig.sizes["gen_max_seq_len"]))), np.int32)
        ids[0, :len(seq)] = seq
        at = r["prompt_len"] - 1 + np.arange(len(tokens))
        rows = np.asarray(reference.logits(params, ids, **kwargs))[0][at]
        low = np.asarray(reference.logits(params, ids, precision="bf16",
                                          **kwargs))[0][at]
        best, each = rows.max(-1), np.arange(len(at))
        gaps = {"program": best - rows[each, tokens],
                "bf16": best - rows[each, low.argmax(-1)]}
        quarters = np.array_split(each, min(4, len(each)))
        rig.run.say("gap_profile", prompt_len=r["prompt_len"],
                    n_tokens=len(tokens), **{
                        who: [round(float(g[q].max()), 4) for q in quarters]
                        for who, g in gaps.items()},
                    off_best={who: int((took != rows.argmax(-1)).sum())
                              for who, took in (("program", tokens),
                                                ("bf16", low.argmax(-1)))})


def run(run: harness.Run) -> harness.Outcome:
    if run.trace:
        rig_gaps = serving_rig.ServingRig.served_gaps

        def served_gaps(rig, sample, params, control):
            _gap_profile(rig, sample, params)
            return rig_gaps(rig, sample, params, control)

        serving_rig.ServingRig.served_gaps = served_gaps
    limits = run.cell["limits"]
    for name, spec in limits.items():
        if name not in _RIG_NAME:
            raise ValueError(f"the cell states a limit for {name!r}; this "
                             f"driver sets {sorted(_RIG_NAME)}")
        if not spec["program"] < spec["limit"] < spec["control"]:
            raise ValueError(f"the limit of {name} ({spec['limit']}) does not "
                             f"lie between the program's reading "
                             f"({spec['program']}) and the control's "
                             f"({spec['control']})")
        setattr(serving_rig, _RIG_NAME[name], float(spec["limit"]))
    run.say("limits", **{name: spec["limit"] for name, spec in limits.items()})
    return gen_open_loop.run(run)
