"""A Mosaic kernel's share of its roofline in the traced window, in percent,
with the counts of a module the metric's file names (``hybrid_roofline`` with
the configuration's counts told, not imported by name): the least time the
chip could take over the time the kernel's events took.

params: ``counts`` (a module of ``benchmark``: ``state_bytes_per_slot_step``,
``chunk_pass``, ``kv_bytes_per_token``), ``kernel`` (the instruction name
whose events are summed) and ``need``, what the least time is computed from:

* ``state_bytes``: the state the decode steps dispatched in the traced window
  had to read and write, from the program's counter of live slot-steps
  (``zoo_gen_decode_slot_steps_total`` at the window's two ends);
* ``chunk_pass``: each call's products and operands at its shape, the larger
  of operations over peak FLOP/s and bytes over peak bytes/s;
* ``kv_read``: keys and values of every cached token each decode step of the
  traced window read, all layers (``kernel_roofline.kv_tokens_read``).

A program without the kernel or the counter (one older than the metric)
gives nothing to read."""

import importlib

from benchmark.readers import kernel_roofline, xplane

SLOT_STEPS = "zoo_gen_decode_slot_steps_total"


def read(obs, params):
    trace = obs.get("trace")
    if trace is None or not trace.devices or "peaks" not in obs:
        return None
    counts = importlib.import_module("benchmark." + params["counts"])
    config, peaks = obs["config"], obs["peaks"]
    shares = []
    for device in trace.devices:
        events = xplane.kernel_ops(device, [params["kernel"]])
        seconds = sum(o.end - o.start for o in events)
        if not seconds:
            continue
        if params["need"] == "state_bytes":
            if SLOT_STEPS not in obs.get("trace_counters1", {}):
                return None
            steps = obs["trace_counters1"][SLOT_STEPS] \
                - obs["trace_counters0"].get(SLOT_STEPS, 0.0)
            least = steps * counts.state_bytes_per_slot_step(config) \
                / peaks["hbm_bytes_per_s"]
        elif params["need"] == "chunk_pass":
            least = 0.0
            for o in events:
                flops, nbytes = counts.chunk_pass(config, o.shape)
                least += max(flops / peaks["flops_per_s_bf16"],
                             nbytes / peaks["hbm_bytes_per_s"])
        elif params["need"] == "kv_read":
            least = kernel_roofline.kv_tokens_read(obs) \
                * counts.kv_bytes_per_token(config) / peaks["hbm_bytes_per_s"]
        else:
            raise ValueError(f"no such need {params['need']!r}")
        shares.append(100.0 * least / seconds)
    return sum(shares) / len(shares) if shares else None
