"""Increase of one of the program's counters over the measured window, per
second of it (for a counter of seconds, a share of the window).

params: ``counter`` (the flattened name, see ``harness.counters``)."""


def read(obs, params):
    name = params["counter"]
    if name not in obs["counters1"]:
        return None
    t0, t1 = obs["window"]
    delta = obs["counters1"][name] - obs["counters0"].get(name, 0.0)
    return delta / (t1 - t0)
