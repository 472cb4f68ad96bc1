"""A Mosaic kernel's share of its roofline in the traced window, in percent:
the least time the chip could take (operations over peak FLOP/s, or bytes over
peak bytes/s, whichever the kernel is bound by) over the time its events took.

params: ``kernels`` (instruction names whose time is summed), and either
``flops`` (a function of ``benchmark.flops`` applied to the output shape of
each event of ``count_on``) or ``bytes: "kv_read"`` (keys and values of every
cached token each decode step of the traced window read, all layers)."""

from benchmark import flops
from benchmark.readers import xplane


def kv_tokens_read(obs) -> float:
    """Cached tokens attended to by the decode steps inside the traced window:
    the j-th output token of a request (j >= 2) comes from a step that read
    its prompt and the j - 1 tokens before it."""
    t0, t1 = obs["trace_span"]
    tokens = 0.0
    for r in obs.get("records", ()):
        j = 0
        for t, k in r["frames"]:
            for _ in range(k):
                j += 1
                if j >= 2 and t0 <= t <= t1:
                    tokens += r["prompt_len"] + j - 1
    return tokens


def read(obs, params):
    trace = obs.get("trace")
    if trace is None or not trace.devices:
        return None
    peaks = obs["peaks"]
    shares = []
    for device in trace.devices:
        events = xplane.kernel_ops(device, params["kernels"])
        seconds = sum(o.end - o.start for o in events)
        if not seconds:
            continue
        if "flops" in params:
            work = getattr(flops, params["flops"])
            need = sum(work(o.shape) for o in events
                       if params["count_on"] in o.name)
            least = need / peaks["flops_per_s_bf16"]
        else:
            need = kv_tokens_read(obs) * obs["config"]["n_layer"] \
                * flops.kv_bytes_per_token(obs["config"])
            least = need / peaks["hbm_bytes_per_s"]
        shares.append(100.0 * least / seconds)
    return sum(shares) / len(shares) if shares else None
