"""What every driver, reader and tool of the benchmark shares.

Nothing here knows a cell, a configuration or a metric by name: each of those
is a file that ``run.py`` finds by the name ``BENCHMARK.json`` gives it
(``workloads/<cell>.json``, ``configs/<config>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.json``, ``drivers/<kind>.py``, ``readers/<reader>.py``).
"""

from __future__ import annotations

import importlib
import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def load(kind: str, name: str) -> Dict[str, Any]:
    """``<kind>/<name>.json`` of the benchmark, e.g. ``load("configs", ...)``."""
    path = os.path.join(HERE, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, rehearse: bool = False):
    """A cell with the configuration and the traffic mix it names, each with
    its ``rehearse`` overrides merged in for the CPU rehearsal (which also
    sends the kernels a TPU takes by default through the interpreter)."""
    cell = rehearsal(load("workloads", name), rehearse)
    config = rehearsal(load("configs", cell["config"]), rehearse)
    mix = rehearsal(load("traffic", cell["traffic"]), rehearse)
    if rehearse:
        os.environ["ZOO_PAGED_ATTENTION"] = "on"
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    return cell, config, mix


def resolve(spec: Any, table: Dict[str, Any]) -> Any:
    """``"@key"`` strings become ``table[key]``; containers are walked."""
    if isinstance(spec, str) and spec.startswith("@"):
        return table[spec[1:]]
    if isinstance(spec, dict):
        return {k: resolve(v, table) for k, v in spec.items()}
    if isinstance(spec, list):
        return [resolve(v, table) for v in spec]
    return spec


def named(path: str):
    """``"package.module:attribute"`` -> the attribute."""
    module, attr = path.split(":")
    return getattr(importlib.import_module(module), attr)


def construct(spec: Dict[str, Any], table: Dict[str, Any]):
    """``{"constructor": "mod:Name", "kwargs": {...}}`` -> ``Name(**kwargs)``."""
    return named(spec["constructor"])(**resolve(spec.get("kwargs", {}), table))


def build_model(config: Dict[str, Any]):
    return construct(config["build"], config)


def reference_of(config: Dict[str, Any]):
    """The configuration's plain reference: ``(module, kwargs)``."""
    ref = config["reference"]
    module = importlib.import_module("benchmark.reference." + ref["module"])
    return module, resolve(ref.get("kwargs", {}), config)


def make_context(config: Dict[str, Any]):
    """The ``ZooContext`` a user of this deployment builds first."""
    from analytics_zoo_tpu.common import (MeshConfig, PrecisionConfig,
                                          RuntimeConfig, init_zoo_context,
                                          reset_zoo_context)

    reset_zoo_context()
    return init_zoo_context(RuntimeConfig(
        mesh=MeshConfig(**config.get("mesh", {"dp": 0})),
        precision=PrecisionConfig(**config.get("precision", {}))))


def make_params(model, seed: int):
    """The model's own initialiser, run on the device in one jitted call."""
    import jax

    return jax.jit(lambda key: model.build(key)[0])(jax.random.PRNGKey(seed))


def rehearsal(spec: Dict[str, Any], on: bool) -> Dict[str, Any]:
    """``spec`` with its ``"rehearse"`` overrides merged in (one level deep
    for dict values), for the tiny CPU rehearsal; without them otherwise."""
    out = {k: v for k, v in spec.items() if k != "rehearse"}
    if on:
        for key, value in spec.get("rehearse", {}).items():
            if isinstance(value, dict) and isinstance(out.get(key), dict):
                out[key] = {**out[key], **value}
            else:
                out[key] = value
    return out


def peaks_for(kind: str) -> Dict[str, Any]:
    """Published peaks of the exact ``device_kind``; unknown is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks on record for device kind {kind!r}; add it "
                       f"to benchmark/peaks.json with its source")
    return table[kind]


# --------------------------------------------------------------- counters

def counters() -> Dict[str, float]:
    """The program's telemetry, flattened: ``name{labels}`` -> value, and for
    a histogram ``name{labels}:sum`` and ``:count``."""
    from analytics_zoo_tpu.common import telemetry

    flat: Dict[str, float] = {}
    for name, family in telemetry.snapshot().items():
        for labels, sample in family["samples"].items():
            key = f"{name}{{{labels}}}" if labels else name
            if isinstance(sample, dict):
                flat[key + ":sum"] = float(sample["sum"])
                flat[key + ":count"] = float(sample["count"])
            else:
                flat[key] = float(sample)
    return flat


class CompileCounter:
    """Counts the executables JAX builds (from the persistent cache or not),
    through its own monitoring events: the window must add none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1


# ----------------------------------------------------------------- tracing

class TraceWindow:
    """Profiles ``duration_s`` seconds, ``start_after_s`` into the measured
    window, from a thread of its own, so that the window's driver (a blocking
    ``fit``, a serving loop) needs no hook. Counter snapshots are taken at
    both ends, for metrics that divide device time by work done."""

    def __init__(self, out_dir: str, start_after_s: float, duration_s: float):
        self.dir = os.path.join(out_dir, "trace")
        self.start_after_s = start_after_s
        self.duration_s = duration_s
        self.t_start = self.t_stop = None       # time.monotonic()
        self.counters_start: Dict[str, float] = {}
        self.counters_stop: Dict[str, float] = {}
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-trace-window")
        self._cancel = threading.Event()
        self.error: Optional[BaseException] = None

    def start(self) -> "TraceWindow":
        self._thread.start()
        return self

    def _run(self) -> None:
        import jax

        if self._cancel.wait(self.start_after_s):
            return
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # TraceMe spans only
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_start = time.monotonic()
            self.counters_start = counters()
            self._cancel.wait(self.duration_s)
            self.counters_stop = counters()
            self.t_stop = time.monotonic()
            jax.profiler.stop_trace()
        except BaseException as e:      # surfaced by finish(), on the caller
            self.error = e

    def finish(self) -> Dict[str, Any]:
        """Wait for the trace; what the readers need of it, as observations:
        the ``.xplane.pb`` written (if any), the traced span on the
        ``time.monotonic()`` clock and the counters at its two ends."""
        import glob

        self._thread.join(timeout=self.start_after_s + self.duration_s + 120)
        if self.error is not None:
            raise self.error
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        return {"trace_path": max(found, key=os.path.getmtime)
                if found else None,
                "trace_span": (self.t_start, self.t_stop),
                "trace_counters0": self.counters_start,
                "trace_counters1": self.counters_stop}


# ------------------------------------------------------------ run context

@dataclass
class Run:
    """One run of one cell, as ``run.py`` hands it to the cell's driver."""

    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    out_dir: str
    t_process_start: float                      # time.monotonic()
    compiles: CompileCounter = None
    #: put the control of the correctness check in the program's place: the
    #: reference in this precision below the configuration's, which has to
    #: come out not correct; no run of the driver's does
    control: Optional[str] = None

    def say(self, phase: str, **fields) -> None:
        body = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f"[{self.cell['name']} +{time.monotonic() - self.t_process_start:6.1f}s"
              f" {phase}] {body}", flush=True)

    def trace_seconds(self) -> float:
        """How long a traced run profiles (the mix's file says; 0 untraced)."""
        if not self.trace:
            return 0.0
        return min(float(self.traffic.get("trace", {}).get("seconds", 4.0)),
                   self.seconds)

    def trace_window(self, start_after_s: Optional[float] = None
                     ) -> Optional[TraceWindow]:
        """The profiler's window of a traced run, started now: after the
        mix's ``trace.start_after_s`` (inside the measured window), or after
        ``start_after_s`` where the driver places it itself."""
        if not self.trace:
            return None
        duration = self.trace_seconds()
        if start_after_s is None:
            start_after_s = min(
                float(self.traffic.get("trace", {}).get("start_after_s", 2.0)),
                max(0.0, self.seconds - duration))
        return TraceWindow(self.out_dir, start_after_s, duration).start()


@dataclass
class Outcome:
    """What a driver returns. ``observations`` is everything a per-layer
    reader may want: counter snapshots, the trace, request records, and
    ``memory_peak_bytes`` where the driver read the peak itself (before a
    reference ran on the chip)."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    observations: Dict[str, Any] = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)
    #: each number that decided ``correct``: name -> [value, limit]
    checks: Dict[str, List[float]] = field(default_factory=dict)


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by the nearest-rank rule: always one
    of the readings, so a tail never interpolates toward a value not seen."""
    import numpy as np

    return float(np.percentile(list(values), q, method="inverted_cdf"))
