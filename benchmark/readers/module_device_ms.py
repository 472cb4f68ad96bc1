"""Device time of chosen executables in the traced window, in milliseconds:
per run of the executable, or per 1,000 units of a counter's increase.

params: ``module`` (regular expression on the HLO module name), ``has_op`` /
``lacks_op`` (an instruction name the executable holds or lacks: jitted
lambdas share one module name), and either nothing (per run) or
``per_1000_of`` (a counter). Averaged over the chips in the trace."""

from benchmark.readers import xplane


def read(obs, params):
    trace = obs.get("trace")
    if trace is None or not trace.devices:
        return None
    seconds = n_runs = 0.0
    for device in trace.devices:
        chosen = xplane.runs(device, params.get("module"),
                             params.get("has_op"), params.get("lacks_op"))
        n_runs += len(chosen)
        seconds += sum(xplane.total(xplane.union(xplane.spans(r.ops)))
                       for r in chosen)
    if not n_runs:
        return None
    counter = params.get("per_1000_of")
    if counter is None:
        return 1e3 * seconds / n_runs
    done = obs["trace_counters1"].get(counter, 0.0) \
        - obs["trace_counters0"].get(counter, 0.0)
    if done <= 0:
        return None
    return 1e3 * (seconds / len(trace.devices)) / (done / 1e3)
