"""Mean share of decode slots occupied per decode step inside the window,
from ``ContinuousBatcher.stats()`` at its two ends (the program reports the
ratio since start, and the step count that weighs it)."""


def read(obs, params):
    a, b = obs.get("stats0"), obs.get("stats1")
    if not a or not b or b["steps"] == a["steps"]:
        return None
    occupied = b["slot_occupancy"] * b["steps"] - a["slot_occupancy"] * a["steps"]
    return occupied / (b["steps"] - a["steps"])
