"""Increase, over the measured window, of the sum of the program's counters
that match ``num``, over the increase of those that match ``den`` (without
``den``: over the window's seconds), times ``scale``.

params: ``num`` and ``den`` (lists of regular expressions, matched whole
against the flattened names of ``harness.counters``, where a label's value is
part of the name: ``zoo_gen_loop_seconds_total{idle}``,
``zoo_gen_prefill_seconds{256}:sum``), ``scale`` (default 1). A program
without the counters (one older than the metric) gives nothing to read."""

import re


def increase(obs, patterns):
    """Summed increase of the matching counters, or None if none is there."""
    names = [n for n in obs["counters1"]
             if any(re.fullmatch(p, n) for p in patterns)]
    if not names:
        return None
    return sum(obs["counters1"][n] - obs["counters0"].get(n, 0.0)
               for n in names)


def read(obs, params):
    num = increase(obs, params["num"])
    if "den" in params:
        den = increase(obs, params["den"])
    else:
        t0, t1 = obs["window"]
        den = t1 - t0
    if num is None or not den:
        return None
    return params.get("scale", 1.0) * num / den
