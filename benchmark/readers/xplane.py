"""From a profiler trace (``.xplane.pb``) to intervals the metric readers use.

Written against traces of this program on a TPU v5e (recorded ones are in
``benchmark/tests/data``; ``benchmark/tools/trace_probe.py`` prints their
structure). What it relies on:

* every chip is a plane ``/device:TPU:<n>`` with the lines ``XLA Modules``
  (one event per run of an executable, named ``<hlo module>(<fingerprint>)``,
  e.g. ``jit_step(...)``, ``jit__lambda(...)``), ``XLA Ops`` (one event per
  HLO instruction that ran, named by its HLO text,
  ``%zoo_flash_fwd.3 = (bf16[4,2048,128]{...}, ...) custom-call(...)``) and
  ``Async XLA Ops`` (asynchronous copies, slices and collectives, from start
  to done);
* a Mosaic kernel is an op whose instruction name starts with the kernel's
  ``name`` (``zoo_flash_fwd``, ``zoo_flash_bwd_dq``, ``zoo_flash_bwd_dkv``,
  ``zoo_paged_attention``);
* host threads are lines of the plane ``/host:CPU``, on the same clock as the
  device lines. A line is named after its thread, and a thread that Python
  started after the process (``python`` under ``python run.py``, ``python3``
  under the contract's ``python3 run.py``), so the lines of the program's own
  threads are taken by what they hold: a telemetry span
  (``serving.gen.loop.decode_wait`` ... enter a ``TraceAnnotation``) or an
  event that only the interpreter's side of JAX emits (``PjitFunction(step)``,
  ``np.asarray(jax.Array)``). The runtime's own threads (transfers, compile
  passes) hold neither and stay out of ``idle_gaps``.

Times are seconds from the start of the trace.
"""

from __future__ import annotations

import bisect
import collections
import gzip
import heapq
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

_INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*\s*=\s*\(?"
                          r"([a-z]+\d*\[[\d,]*\])?")
_MODULE = re.compile(r"^(.*?)\((\d+)\)$")
#: what this program's telemetry spans look like, among the host events
_SPAN = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
#: events that JAX emits from the thread of the Python caller alone
_PYTHON_SIDE = re.compile(r"^(PjitFunction\(|np\.asarray\(|"
                          r"PythonRefManager::|ParseArguments$)")
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast)")


@dataclass
class Op:
    start: float
    end: float
    name: str           # HLO instruction name without its numeric suffix
    shape: str          # first output, e.g. "bf16[4,2048,128]" ("" if none)


@dataclass
class ModuleRun:
    start: float
    end: float
    name: str           # HLO module name, e.g. "jit_step"
    fingerprint: str
    ops: List[Op] = field(default_factory=list)


@dataclass
class Device:
    name: str
    modules: List[ModuleRun]
    ops: List[Op]
    async_ops: List[Op]


@dataclass
class Trace:
    devices: List[Device]
    host: List[Tuple[float, float, str]]    # events of the program's threads
    #: first start and last end over every event of every plane and line:
    #: the part of the profiling session the trace itself vouches for
    span: Interval = (0.0, 0.0)


def _op(event) -> Op:
    m = _INSTRUCTION.match(event.name)
    start = event.start_ns * 1e-9
    return Op(start, start + event.duration_ns * 1e-9,
              m.group(1) if m else event.name.split(" ")[0].lstrip("%"),
              (m.group(2) or "") if m else "")


def load(path: str) -> Trace:
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return from_xspace(f.read())


def from_xspace(serialized: bytes) -> Trace:
    """A serialized ``XSpace`` (the content of an ``.xplane.pb``)."""
    import jax

    data = jax.profiler.ProfileData.from_serialized_xspace(serialized)
    devices, host = [], []
    first, last = float("inf"), 0.0
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                first = min(first, e.start_ns)
                last = max(last, e.start_ns + e.duration_ns)
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            modules = []
            for e in (lines["XLA Modules"].events
                      if "XLA Modules" in lines else ()):
                m = _MODULE.match(e.name)
                start = e.start_ns * 1e-9
                modules.append(ModuleRun(
                    start, start + e.duration_ns * 1e-9,
                    m.group(1) if m else e.name, m.group(2) if m else ""))
            modules.sort(key=lambda r: r.start)
            ops = sorted((_op(e) for e in (lines["XLA Ops"].events
                                           if "XLA Ops" in lines else ())),
                         key=lambda o: o.start)
            async_ops = sorted(
                (_op(e) for e in (lines["Async XLA Ops"].events
                                  if "Async XLA Ops" in lines else ())),
                key=lambda o: o.start)
            starts = [r.start for r in modules]
            for op in ops:      # an op belongs to the run it started inside
                i = bisect.bisect_right(starts, op.start) - 1
                if i >= 0 and op.start < modules[i].end:
                    modules[i].ops.append(op)
            devices.append(Device(plane.name, modules, ops, async_ops))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [(e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9, e.name)
                          for e in line.events]
                if any(_SPAN.match(name) or _PYTHON_SIDE.match(name)
                       for _, _, name in events):
                    host.extend(events)
    devices.sort(key=lambda d: d.name)
    host.sort()
    return Trace(devices, host,
                 (first * 1e-9, last * 1e-9) if last >= first else (0.0, 0.0))


# -------------------------------------------------------- interval algebra

def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of ``a`` that no interval of ``b`` covers (both merged)."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def spans(ops: Iterable[Op]) -> List[Interval]:
    return [(o.start, o.end) for o in ops]


# --------------------------------------------------------------- reductions

def window_s(trace: Trace, host_clock_s: float = 0.0) -> float:
    """Length of the traced window: the span of the trace's own events, or
    the time the host's clock saw between ``start_trace`` returning and
    ``stop_trace`` being called, whichever is longer. The profiler records
    from inside the first call to inside the second, so the host's reading
    alone is a few milliseconds short of what was recorded, and a chip that
    never idles would show busier than the window is long. Every op lies
    inside the span, so ``busy_s`` is never above this."""
    return max(trace.span[1] - trace.span[0], host_clock_s)


def busy_s(trace: Trace) -> float:
    """Seconds in which an op ran, averaged over the chips in the trace."""
    if not trace.devices:
        return 0.0
    return sum(total(union(spans(d.ops))) for d in trace.devices) \
        / len(trace.devices)


def runs(device: Device, module: Optional[str] = None,
         has_op: Optional[str] = None,
         lacks_op: Optional[str] = None) -> List[ModuleRun]:
    """Runs of executables chosen by what can be observed of them: the HLO
    module's name (a regular expression, matched whole: an alternation
    ``jit__lambda|jit_zoo_gen_decode_step`` takes a step under its old and its new
    name) and an instruction name they hold or lack (jitted lambdas all share
    one module name)."""
    out = []
    for run in device.modules:
        if module and not re.fullmatch(module, run.name):
            continue
        names = {op.name for op in run.ops}
        if has_op and has_op not in names:
            continue
        if lacks_op and lacks_op in names:
            continue
        out.append(run)
    return out


def kernel_ops(device: Device, names: Sequence[str]) -> List[Op]:
    """Events of the Mosaic kernels named: the instruction's name holds the
    kernel's (inside ``shard_map`` it is ``jvp_zoo_flash_fwd_``)."""
    return [op for op in device.ops if any(k in op.name for k in names)]


def collective_exposed_s(device: Device) -> float:
    """Seconds in which a collective was in flight on this chip and no other
    op ran: what the step waits for."""
    coll = [o for o in device.ops + device.async_ops
            if COLLECTIVE.match(o.name)]
    compute = [o for o in device.ops if not COLLECTIVE.match(o.name)]
    return total(subtract(union(spans(coll)), union(spans(compute))))


def device_ops(trace: Trace, top: int = 10) -> List[List]:
    """The ops that took most device time, summed over chips: a kernel under
    its Mosaic name, anything else as ``<module>/<instruction> <output>``."""
    if not trace.devices:
        return []
    acc: Dict[str, float] = collections.defaultdict(float)
    for device in trace.devices:
        for run in device.modules:
            for op in run.ops:
                label = (op.name if "zoo_" in op.name
                         else f"{run.name}/{op.name} {op.shape}".strip())
                acc[label] += op.end - op.start
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, seconds / len(trace.devices)] for name, seconds in ranked]


def _innermost_at(events: Sequence[Tuple[float, float, str]],
                  times: Sequence[float]) -> List[str]:
    """For each instant of ``times`` (ascending), the name of the event open
    then that began last (``none`` where none is open). ``events`` are
    ``(start, end, name)`` in order of their starts."""
    heap: List[Tuple[float, float, str]] = []
    out, i = [], 0
    for t in times:
        while i < len(events) and events[i][0] <= t:
            start, end, name = events[i]
            heapq.heappush(heap, (-start, end, name))
            i += 1
        while heap and heap[0][1] < t:      # the latest to begin has ended
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else "none")
    return out


def idle_gaps(trace: Trace, top: int = 10,
              short_s: float = 50e-6) -> List[List]:
    """Idle time of the first chip by what the host was doing: each gap
    between ops is cut where an event of the program's threads begins or
    ends, and each piece goes to ``<telemetry span>/<innermost other event>``
    open on a thread of the program during it (``none`` where there is none);
    gaps under ``short_s`` go to one entry of their own. A gap is shared out
    by time and not handed whole to what was open at its middle: a decode
    loop's gap runs from the end of one phase through two or three short
    ones into the next, and the short one in the middle would be given
    milliseconds it never lasted (PR 34: ``emit`` read 0.62 s of a traced 4 s
    in which the loop's own clock gave it 0.07)."""
    if not trace.devices:
        return []
    import numpy as np

    merged = union(spans(trace.devices[0].ops))
    acc: Dict[str, float] = collections.defaultdict(float)
    cuts = np.sort(np.array([t for h in trace.host for t in h[:2]]))
    pieces = []                                 # (middle, seconds)
    for (_, a), (b, _) in zip(merged, merged[1:]):
        if b - a < short_s:
            acc[f"gaps_under_{int(short_s * 1e6)}us"] += b - a
            continue
        inner = cuts[np.searchsorted(cuts, a, "right"):
                     np.searchsorted(cuts, b, "left")]
        edges = np.concatenate([[a], inner, [b]])
        pieces.extend(zip((edges[:-1] + edges[1:]) / 2, np.diff(edges)))
    pieces.sort()
    middles = [m for m, _ in pieces]
    in_span = _innermost_at(
        [h for h in trace.host if _SPAN.match(h[2])], middles)
    in_other = _innermost_at(
        [h for h in trace.host if not _SPAN.match(h[2])], middles)
    for (_, seconds), span, other in zip(pieces, in_span, in_other):
        acc[f"{span}/{other}"] += float(seconds)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, seconds] for name, seconds in ranked]
