"""Share of the traced window in which a collective was in flight and nothing
else ran, on the chip where that is worst."""

from benchmark.readers import xplane


def read(obs, params):
    trace = obs.get("trace")
    if trace is None or not trace.devices:
        return None
    return max(xplane.collective_exposed_s(d) for d in trace.devices) \
        / obs["window_s"]
