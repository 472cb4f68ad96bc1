"""Collective-budget rules: the ZeRO-1 one-exchange-per-bucket gate.

The flat update-sharding path (``parallel/update_sharding.py``) is
structurally one exchange per bucket per global step — a bucket-sized
reduce-scatter and a params all-gather for each bucket of the flat meta —
with counts constant in ``grad_accum_steps``. This module owns both counters
that guard it:

* :func:`collective_counts` — the compiled-HLO instruction counter
  (migrated here from ``parallel.update_sharding``; the bench's
  ``--update-sharding`` gate and the HLO-layer rule run on it). Counts
  *instruction definitions* only, so operand mentions don't double-count;
  also recognizes lowered StableHLO spellings.
* :func:`entry_ops` — the compiled program's top-level ops by kind, with
  the bytes each kind writes: what a layout costs in copies of its own
  (standalone ``reshape``, ``concatenate``, ``slice``, ``convert``) beside
  the collectives it asked for.
* :func:`jaxpr_collective_counts` — the trace-time counter
  (``TrainConfig.graph_checks`` runs before anything compiles). Primitive
  names are normalized to the HLO spellings so one ``expect_collectives``
  dict drives both layers. Collectives inside scan/while bodies are tallied
  separately: an in-loop gradient collective executes once per microbatch —
  exactly the cost the accumulation scan exists to amortize away.
"""

from __future__ import annotations

import math
import re
from collections import Counter
from typing import Dict, Iterable, List

from ..core import Finding, Rule, RuleContext, register
from ..graphlint import walk_eqns

_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)"
    r"(?:-start)?\(")
# lowered-but-not-compiled StableHLO text spells them differently
_STABLEHLO_RE = re.compile(
    r"\bstablehlo\.(all_reduce|reduce_scatter|all_gather|collective_permute"
    r"|all_to_all)\b")

#: jax primitive name -> HLO instruction spelling
_PRIMITIVE_TO_HLO = {
    "psum": "all-reduce",
    "pmax": "all-reduce",
    "pmin": "all-reduce",
    "reduce_scatter": "reduce-scatter",
    "psum_scatter": "reduce-scatter",
    "all_gather": "all-gather",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
    "pgather": "all-gather",
}


def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Count collective *instruction definitions* in compiled HLO (or
    lowered StableHLO) text, e.g. ``{"reduce-scatter": 1, "all-gather": 1}``
    (ignores mentions in operand positions). Lowered text defines a jitted
    function once however often it is called, so the buckets of the flat
    exchange count once there and ``n_buckets`` times once compiled."""
    out: Counter = Counter()
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        rhs = line.split("=", 1)[1]
        m = _COLLECTIVE_RE.search(rhs)
        if m:
            out[m.group(1)] += 1
            continue
        m = _STABLEHLO_RE.search(rhs)
        if m:
            out[m.group(1).replace("_", "-")] += 1
    return dict(out)


_ENTRY_OP_RE = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([\w\-]+)\(")
_SHAPE_RE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")


def shape_bytes(shape_text: str) -> int:
    """Bytes an HLO result type holds, e.g. ``f32[8,128]{1,0}`` or a tuple
    of such (every array shape in the text is summed)."""
    size = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        bits = 8 if dtype == "pred" else int(dtype.lstrip("sufb"))
        size += bits // 8 * math.prod(int(d) for d in dims.split(",") if d)
    return size


def entry_ops(hlo_text: str) -> Dict[str, List[int]]:
    """``{kind: [count, output bytes]}`` of the ops of compiled HLO's
    ``ENTRY`` computation (a fusion counts as ``fusion``, its body is not
    opened). The bytes are what the ops' result shapes hold, tuples summed;
    they are not a time, and an op that updates a buffer in place counts
    the whole buffer."""
    entry = hlo_text[hlo_text.index("ENTRY"):]
    out: Dict[str, List[int]] = {}
    for line in entry[:entry.index("\n}")].splitlines():
        m = _ENTRY_OP_RE.match(line)
        if m:
            tally = out.setdefault(m.group(2), [0, 0])
            tally[0] += 1
            tally[1] += shape_bytes(m.group(1))
    return out


def jaxpr_collective_counts(closed_jaxpr) -> Dict[str, Dict[str, int]]:
    """Trace-time collective census: ``{"counts": {...}, "in_loop": {...}}``
    with HLO-normalized keys. ``in_loop`` tallies collectives sitting inside
    scan/while bodies (they run once per loop iteration)."""
    counts: Counter = Counter()
    in_loop: Counter = Counter()
    for site in walk_eqns(closed_jaxpr.jaxpr):
        if site.in_kernel:
            continue
        hlo = _PRIMITIVE_TO_HLO.get(site.eqn.primitive.name)
        if hlo is None:
            continue
        counts[hlo] += 1
        if site.in_loop:
            in_loop[hlo] += 1
    return {"counts": dict(counts), "in_loop": dict(in_loop)}


def _budget_findings(rule: Rule, ctx: RuleContext, counts: Dict[str, int],
                     in_loop: Dict[str, int]) -> List[Finding]:
    """Compare counts against ``ctx.expect_collectives`` (only listed keys
    are compared — incidental all-reduces like a loss pmean don't trip a
    reduce-scatter budget)."""
    out: List[Finding] = []
    if ctx.expect_collectives:
        for key, want in ctx.expect_collectives.items():
            got = counts.get(key, 0)
            if got != want:
                out.append(rule.emit(
                    ctx, f"collective budget violated: expected {want} "
                         f"{key} per step, found {got}",
                    expected=want, found=got, collective=key))
    for key, n in in_loop.items():
        if ctx.expect_collectives is None or key not in ctx.expect_collectives:
            continue
        out.append(rule.emit(
            ctx, f"{n} {key} inside a scan/while body — cost scales with "
                 f"the loop trip count (grad accumulation must keep the "
                 f"gradient exchange outside the microbatch scan)",
            collective=key, in_loop=n))
    return out


@register
class CollectiveBudgetRule(Rule):
    """Trace-time (jaxpr) collective budget vs ``ctx.expect_collectives``."""

    id = "collective-budget"
    layer = "jaxpr"
    severity = "error"
    doc = ("Collective census of the traced step vs an expected budget "
           "(e.g. ZeRO-1 flat: one exchange per bucket per global step — "
           "n_buckets reduce-scatters + n_buckets all-gathers, constant in "
           "grad_accum_steps, none inside the accumulation scan)")

    def check(self, closed_jaxpr, ctx: RuleContext) -> Iterable[Finding]:
        if ctx.expect_collectives is None:
            return []
        census = jaxpr_collective_counts(closed_jaxpr)
        return _budget_findings(self, ctx, census["counts"],
                                census["in_loop"])


@register
class HloCollectiveBudgetRule(Rule):
    """Post-compile (HLO) collective budget vs ``ctx.expect_collectives`` —
    catches partitioner-inserted collectives the jaxpr never shows."""

    id = "collective-budget-hlo"
    layer = "hlo"
    severity = "error"
    doc = ("Collective instruction count of compiled HLO vs an expected "
           "budget (the bench --update-sharding gate)")

    def check(self, hlo_text: str, ctx: RuleContext) -> Iterable[Finding]:
        if ctx.expect_collectives is None:
            return []
        return _budget_findings(self, ctx, collective_counts(hlo_text), {})
