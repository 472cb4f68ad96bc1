"""Rule engine core: findings, rules, registry, enforcement.

The platform carries structural invariants that used to be enforced by
one-off walkers buried in a benchmark script — ZeRO-1's one-reduce-scatter/
one-all-gather budget (PR 5), the fused-int8 no-HBM-intermediate guarantee
(PR 6), the bf16/f32 dtype discipline. This module is the shared substrate
those checks now run on: a :class:`Rule` walks an artifact (a traced jaxpr,
compiled HLO text, a recorded signature history, or Python source) and emits
structured :class:`Finding`\\ s; callers decide whether findings warn, raise,
or fail a CI gate.

Layers (``Rule.layer``):

* ``"jaxpr"`` — the rule's ``check`` receives a ``jax.core.ClosedJaxpr``
  (see :mod:`analysis.graphlint` for tracing helpers and the recursive
  equation walker that knows which equations live inside pallas kernels).
* ``"hlo"`` — ``check`` receives compiled HLO (or lowered StableHLO) text.
* ``"signatures"`` — ``check`` receives an iterable of dispatch signatures
  recorded at runtime (:class:`analysis.graphlint.SignatureTracker`).
* ``"ast"`` — ``check`` receives a parsed Python module
  (:mod:`analysis.astlint` owns traversal and inline suppressions).

Every emitted finding lands in ``zoo_analysis_findings_total{rule,severity}``
so a fleet can alert on analyzer regressions without parsing lint output.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Type

from ..common import telemetry as _tm

logger = logging.getLogger("analytics_zoo_tpu.analysis")

_FINDINGS = _tm.counter("zoo_analysis_findings_total",
                        "Static-analysis findings emitted (graph + AST "
                        "layers; suppressed findings are not counted)",
                        labels=("rule", "severity"))

#: Severity ladder (ordered weakest → strongest).
SEVERITIES = ("info", "warning", "error")


class GraphLintError(RuntimeError):
    """Raised by :func:`enforce` in ``"raise"`` mode: a graph invariant the
    caller declared load-bearing does not hold. Carries the findings."""

    def __init__(self, findings: Sequence["Finding"]):
        self.findings = list(findings)
        lines = "\n".join(f"  {f}" for f in self.findings)
        super().__init__(
            f"{len(self.findings)} graph-lint finding(s):\n{lines}")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One structured analyzer result."""

    rule: str                     # rule id, e.g. "fused-int8-dispatch"
    severity: str                 # "info" | "warning" | "error"
    location: str                 # "path:line", "jaxpr:<where>", "hlo:<where>"
    message: str
    data: Tuple[Tuple[str, Any], ...] = ()   # structured extras (sorted kv)

    def __str__(self) -> str:
        return f"{self.location}: [{self.severity}] {self.rule}: {self.message}"

    def as_dict(self) -> Dict[str, Any]:
        return {"rule": self.rule, "severity": self.severity,
                "location": self.location, "message": self.message,
                "data": dict(self.data)}


def finding(rule: str, severity: str, location: str, message: str,
            **data) -> Finding:
    """Build a :class:`Finding` (validates severity, normalizes data)."""
    if severity not in SEVERITIES:
        raise ValueError(f"unknown severity {severity!r}")
    return Finding(rule, severity, location, message,
                   tuple(sorted(data.items())))


@dataclasses.dataclass
class RuleContext:
    """Per-run configuration shared by every rule.

    ``where`` prefixes finding locations so a fit-time check reads
    ``jaxpr:estimator.fit`` while a warmup check reads
    ``jaxpr:inference.warmup``. The remaining knobs parameterize individual
    rules; a rule whose knob is unset (``None``) stays silent rather than
    guessing an expectation.
    """

    where: str = ""
    # collective-budget: {"reduce-scatter": 1, ...} — ONLY listed keys are
    # compared, so incidental all-reduces (loss pmean) don't false-positive
    expect_collectives: Optional[Dict[str, int]] = None
    # fused-int8-dispatch: the caller asserts the fused kernel tier should be
    # active for this computation (quantized model + fused_mode() != "off")
    fused_expected: bool = False
    # dtype-discipline: declared compute dtype ("bfloat16") for the region
    compute_dtype: Optional[str] = None
    # large-constant: jaxpr consts at/above this many bytes are flagged
    const_bytes_limit: int = 1 << 20
    # recompile-hazard: distinct dispatch signatures allowed before flagging
    max_signatures: Optional[int] = None
    # decode-shape-stability: the (shape, dtype-name) of every KV-cache leaf
    # the traced decode step carries — the rule asserts each one reappears
    # unchanged among the outputs (cache threaded, no per-step growth) and
    # bounds intermediate sizes by one K pool over all layers (half the
    # leaves' bytes)
    decode_cache_avals: Optional[Sequence[Tuple[Tuple[int, ...], str]]] = None
    # memory tier (analysis/memory.py + rules/memory.py):
    # hbm-budget: declared per-device HBM budget; the static live-range peak
    # (and, via the witness, the measured peak) must stay under it
    hbm_budget_bytes: Optional[int] = None
    # donation truth for the dispatch being linted: one flag per FLATTENED
    # positional arg leaf (jax.jit donate_argnums order) — drives the
    # analyzer's in-place-aliasing credit, cache-alias, and donation-missed
    donated_invars: Optional[Sequence[bool]] = None
    # donation-missed: which flattened arg leaves are DEAD after the call
    # (the caller rebinds/discards them) and therefore donation-eligible
    dead_invars: Optional[Sequence[bool]] = None
    # peak-temporary: byte bound a single HBM temporary may not exceed
    # (None = the largest argument leaf, i.e. "the largest model leaf")
    param_leaf_bytes: Optional[int] = None


class Rule:
    """Base class: subclasses set ``id``/``layer``/``severity`` and implement
    ``check(artifact, ctx) -> Iterable[Finding]``."""

    id: str = ""
    layer: str = ""               # "jaxpr" | "hlo" | "signatures" | "ast"
    severity: str = "error"       # default severity for this rule's findings
    doc: str = ""                 # one-line catalog entry (docs + --list-rules)

    def check(self, artifact: Any, ctx: RuleContext) -> Iterable[Finding]:
        raise NotImplementedError

    def emit(self, ctx: RuleContext, message: str, line: Optional[int] = None,
             severity: Optional[str] = None, **data) -> Finding:
        loc = f"{self.layer}:{ctx.where or '<anon>'}"
        if line is not None:
            loc += f":{line}"
        return finding(self.id, severity or self.severity, loc, message,
                       **data)


_REGISTRY: Dict[str, Rule] = {}

#: historical rule names that generalized into a successor: resolved by
#: :func:`get_rule` and honored by inline ``zoo-lint: disable=`` comments,
#: so pre-migration suppressions and docs stay valid. ``telemetry-lock``
#: (the hard-coded _families/_collectors check) became the inferred
#: guarded-by rule in PR 11.
RULE_ALIASES: Dict[str, str] = {"telemetry-lock": "lock-guarded-by"}


def register(cls: Type[Rule]) -> Type[Rule]:
    """Class decorator: instantiate + register a rule by id."""
    rule = cls()
    if not rule.id or not rule.layer:
        raise ValueError(f"rule {cls.__name__} needs id and layer")
    _REGISTRY[rule.id] = rule
    return cls


def all_rules(layer: Optional[str] = None) -> List[Rule]:
    """Registered rules, optionally filtered by layer. Importing
    :mod:`analysis.rules` populates the registry."""
    from . import rules as _rules  # noqa: F401 (registration side effect)

    out = [r for r in _REGISTRY.values() if layer is None or r.layer == layer]
    return sorted(out, key=lambda r: r.id)


def get_rule(rule_id: str) -> Rule:
    from . import rules as _rules  # noqa: F401

    rule_id = RULE_ALIASES.get(rule_id, rule_id)
    try:
        return _REGISTRY[rule_id]
    except KeyError:
        raise KeyError(f"unknown rule {rule_id!r}; known: "
                       f"{sorted(_REGISTRY)}") from None


def report(findings: Sequence[Finding]) -> List[Finding]:
    """Count findings into ``zoo_analysis_findings_total`` and return them
    (every lint entry point funnels through here exactly once)."""
    for f in findings:
        _FINDINGS.labels(rule=f.rule, severity=f.severity).inc()
    return list(findings)


def enforce(findings: Sequence[Finding], mode: Optional[str],
            log: Optional[logging.Logger] = None) -> List[Finding]:
    """Apply a ``graph_checks``-style policy to findings.

    ``mode``: ``None``/``"off"`` = no-op; ``"warn"`` = log each finding;
    ``"raise"`` = log warnings/infos, raise :class:`GraphLintError` when any
    error-severity finding is present. Returns the findings either way.
    """
    if not mode or mode == "off":
        return list(findings)
    if mode not in ("warn", "raise"):
        raise ValueError(f"graph_checks must be 'off'/'warn'/'raise', "
                         f"got {mode!r}")
    log = log or logger
    errors = [f for f in findings if f.severity == "error"]
    for f in findings:
        if mode == "warn" or f.severity != "error":
            log.warning("graph-lint: %s", f)
    if mode == "raise" and errors:
        raise GraphLintError(errors)
    return list(findings)
