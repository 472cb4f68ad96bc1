"""Decode-shape-stability rule: the KV-cache decode step's structural
invariants.

The continuous batcher's economics rest on the decode step being ONE
compiled executable whose cost is flat in generated length. Three structural
facts about the traced ``decode_step`` make that true, and each has a quiet
failure mode this rule catches at warmup (``ServingConfig.graph_checks``,
alongside the fused-int8 check) instead of at the next bench run:

* **Cache threads through unchanged.** Every cache leaf's (shape, dtype)
  must reappear among the jaxpr outputs. A concatenate-grown cache (the
  naive "append K/V each step" implementation) changes shape per step —
  one XLA recompile per emitted token.
* **No per-step growth.** No equation outside a kernel body may produce an
  intermediate larger than one K pool over all layers (half the declared
  leaves' bytes: the cache holds one K and one V pool per layer): an O(T²)
  score tensor or an accidentally-broadcast gather shows up here. One
  layer's pool is not the yardstick: a small model's logits legitimately
  outweigh it.
* **No host transfers.** A host callback inside the decode step serializes
  the whole multi-slot loop on a host round-trip per token.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

from ..core import Finding, Rule, RuleContext, finding, register
from ..graphlint import walk_eqns
from .graph_hygiene import _HOST_PRIMITIVES


def _aval_key(aval) -> Tuple[Tuple[int, ...], str]:
    return (tuple(getattr(aval, "shape", ())),
            str(getattr(aval, "dtype", "")))


@register
class DecodeShapeStabilityRule(Rule):
    """Active when ``ctx.decode_cache_avals`` names the cache leaves."""

    id = "decode-shape-stability"
    layer = "jaxpr"
    severity = "error"
    doc = ("The traced decode step must thread every KV-cache leaf through "
           "with identical (shape, dtype), produce no intermediate larger "
           "than one K pool over all layers, and contain no host transfers "
           "— the no-recompile/no-O(T^2) contract of KV-cache decoding")

    def check(self, closed_jaxpr, ctx: RuleContext) -> Iterable[Finding]:
        if not ctx.decode_cache_avals:
            return []
        out: List[Finding] = []
        jaxpr = closed_jaxpr.jaxpr

        # (1) cache threading: each declared leaf reappears among outputs
        out_avals: Dict[Tuple, int] = {}
        for v in jaxpr.outvars:
            k = _aval_key(v.aval)
            out_avals[k] = out_avals.get(k, 0) + 1
        leaf_bytes = []
        for shape, dtype in ctx.decode_cache_avals:
            import numpy as np

            n = 1
            for d in shape:
                n *= int(d)
            try:
                itemsize = np.dtype(dtype).itemsize
            except TypeError:
                import ml_dtypes

                itemsize = np.dtype(getattr(ml_dtypes, dtype)).itemsize
            leaf_bytes.append(n * itemsize)
            key = (tuple(shape), dtype)
            if out_avals.get(key, 0) > 0:
                out_avals[key] -= 1
            else:
                out.append(self.emit(
                    ctx, f"cache leaf {dtype}{tuple(shape)} does not "
                         f"reappear among the decode step's outputs — the "
                         f"cache is being grown/reshaped per step (one "
                         f"recompile per emitted token)",
                    shape=tuple(shape), dtype=dtype))
        # one K pool over all layers; a lone leaf is its own limit
        limit = max(max(leaf_bytes), sum(leaf_bytes) // 2) if leaf_bytes else 0

        # (2)+(3): growth bound and host transfers over every equation
        for site in walk_eqns(jaxpr):
            if site.in_kernel:
                continue
            name = site.eqn.primitive.name
            if name in _HOST_PRIMITIVES:
                out.append(self.emit(
                    ctx, f"{name} inside the decode step — a host round-trip "
                         f"per emitted token", primitive=name))
                continue
            if limit:
                for v in site.eqn.outvars:
                    aval = getattr(v, "aval", None)
                    nbytes = _aval_nbytes(aval)
                    if nbytes is not None and nbytes > limit:
                        out.append(self.emit(
                            ctx, f"{name} produces a "
                                 f"{aval.dtype}{tuple(aval.shape)} "
                                 f"intermediate ({nbytes} bytes) larger "
                                 f"than one K pool over all layers ({limit} "
                                 f"bytes) — per-step growth / O(T^2) "
                                 f"recompute shape",
                            primitive=name, nbytes=int(nbytes)))
                        break
        return out


def _aval_nbytes(aval) -> Optional[int]:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return None
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:     # symbolic dim
            return None
    return n * dtype.itemsize


def lint_decode_stability(model, params, cache_cfg, cache, *,
                          top_k: int = 0, spec_k: int = 0,
                          chunk_tokens: int = 0,
                          where: str = "serving.generation",
                          ctx: Optional[RuleContext] = None,
                          donate_cache: Optional[bool] = None,
                          hbm_budget_bytes: Optional[int] = None,
                          note_static_site: Optional[str] = None
                          ) -> List[Finding]:
    """Trace the decode-path executable at the cache's fixed shapes
    (abstract — no compile, no execution) and run the stability rule. This
    is the warmup entry point (``ContinuousBatcher.check_decode_stability``)
    and the bench's decode-lint gate.

    ``spec_k >= 2`` lints the SPECULATIVE verify executable
    (``model.verify_step`` at query length k) instead of the single-token
    ``decode_step`` — the same invariants hold: every cache leaf threads
    through with identical (shape, dtype), no intermediate outgrows the
    cache, no host transfers, and exactly one compiled executable per
    (k, slot-count) since ids (B, k) is the only aval that varies with k.

    ``chunk_tokens > 0`` ADDITIONALLY lints the chunked-prefill executable
    (``model.prefill_chunk`` at B=1, chunk width ``chunk_tokens``, the wide
    page table chunk dispatch uses) under the same invariants — the cache
    threads through unchanged and the chunk donates the pool too (ONE
    compiled chunk shape per (chunk_tokens, slot), no per-chunk copy of the
    pool); its findings are appended to the decode/verify step's.

    ``donate_cache`` states whether the dispatch donates the cache argument;
    when given, the memory tier runs too — ``cache-alias`` (un-donated pool
    ⇒ XLA copies it every step) and ``hbm-budget`` when
    ``hbm_budget_bytes`` is declared. ``note_static_site`` additionally
    records the donation-aware static peak into the runtime memory witness
    (:mod:`analytics_zoo_tpu.common.memwitness`) under that site name."""
    import jax
    import jax.numpy as jnp

    from ..graphlint import lint_jaxpr

    b = cache_cfg.n_slots
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if spec_k >= 2:
        step = model.verify_step
        ids_aval = i32((b, spec_k))
    else:
        step = model.decode_step
        ids_aval = i32((b,))
    closed = jax.make_jaxpr(
        lambda p, c, ids, ln, tb, sd, ti, tp: step(
            p, c, ids, ln, tb, sd, ti, tp, page_size=cache_cfg.page_size,
            top_k=top_k))(
        params, cache, ids_aval, i32((b,)),
        i32((b, cache_cfg.pages_per_slot)),
        jax.ShapeDtypeStruct((b,), jnp.uint32),
        jax.ShapeDtypeStruct((b,), jnp.uint32),
        jax.ShapeDtypeStruct((b,), jnp.float32))
    import jax.tree_util as jtu

    cache_avals = [(tuple(leaf.shape), str(leaf.dtype))
                   for leaf in jtu.tree_leaves(cache)]
    ctx = ctx or RuleContext(where=where)
    updates: dict = {"decode_cache_avals": cache_avals}
    rules = ["decode-shape-stability"]
    if donate_cache is not None:
        n_params = len(jtu.tree_leaves(params))
        n_cache = len(jtu.tree_leaves(cache))
        # flattened positional signature: params, cache, then 6 scalar rows
        updates["donated_invars"] = ([False] * n_params
                                     + [donate_cache] * n_cache
                                     + [False] * 6)
        updates["hbm_budget_bytes"] = hbm_budget_bytes
        rules += ["cache-alias"] + (["hbm-budget"] if hbm_budget_bytes
                                    else [])
    ctx = RuleContext(**{**ctx.__dict__, **updates})
    findings = lint_jaxpr(closed, ctx=ctx, rules=rules)
    if chunk_tokens > 0:
        # the chunked-prefill executable: B=1, fixed chunk width, and the
        # WIDE table (pages_per_slot + chunk_tokens/page_size entries) the
        # dispatch pads with scratch so the final chunk of a max-length
        # prompt never indexes past the row
        wide = (cache_cfg.pages_per_slot
                + chunk_tokens // cache_cfg.page_size)
        chunk_closed = jax.make_jaxpr(
            lambda p, c, ids, nd, nv, tb: model.prefill_chunk(
                p, c, ids, nd, nv, tb,
                page_size=cache_cfg.page_size))(
            params, cache, i32((1, chunk_tokens)), i32((1,)), i32((1,)),
            i32((1, wide)))
        chunk_updates = dict(updates)
        if donate_cache is not None:
            # flattened positional signature: params, cache, then 4 int rows
            chunk_updates["donated_invars"] = (
                [False] * len(jtu.tree_leaves(params))
                + [donate_cache] * len(jtu.tree_leaves(cache))
                + [False] * 4)
        chunk_ctx = RuleContext(**{**ctx.__dict__, **chunk_updates})
        findings = findings + lint_jaxpr(chunk_closed, ctx=chunk_ctx,
                                         rules=rules)
    if note_static_site:
        from ...common import memwitness as _mw

        if _mw.enabled():
            from ..memory import profile_jaxpr

            prof = profile_jaxpr(closed,
                                 donated_invars=ctx.donated_invars)
            _mw.note_static(note_static_site, prof.peak_live_bytes,
                            hbm_budget_bytes)
    return findings


def lint_prefix_write_isolation(pool, row, start: int, *,
                                page_size: int,
                                where: str = "serving.generation"
                                ) -> List[Finding]:
    """Refcounted-aliasing twin of the cache-alias rule, for the HOST side
    of shared-prefix admission: a suffix prefill starting at position
    ``start`` writes K/V into the pages backing positions ``start ..``, so
    every one of those table pages must be EXCLUSIVELY the stream's
    (pool refcount 1). A shared page here means the copy-on-write of the
    boundary page was skipped or mis-indexed — the write would silently
    corrupt every sibling stream (and the cache) mapped onto that page.

    ``pool``: the :class:`~analytics_zoo_tpu.ops.kv_cache.PagePool`;
    ``row``: the stream's page ids in table order; ``start``: the first
    position the suffix dispatch writes. Pages strictly below
    ``start // page_size`` are the read-only shared prefix and are expected
    to carry refcount >= 2 (that is the whole point); they are not flagged.
    Returns one error finding per violating page (empty = isolated)."""
    out: List[Finding] = []
    first_written = int(start) // int(page_size)
    for idx in range(first_written, len(row)):
        page = int(row[idx])
        refs = pool.ref_count(page)
        if refs > 1:
            out.append(finding(
                "prefix-share-isolation", "error", f"pool:{where}",
                f"page {page} (table index {idx}) is written by the suffix "
                f"prefill from position {start} but carries {refs} "
                f"references — shared pages must be copy-on-write before "
                f"any paged_write touches them",
                page=page, table_index=idx, refcount=refs, start=int(start)))
    return out


__all__ = ["DecodeShapeStabilityRule", "lint_decode_stability",
           "lint_prefix_write_isolation"]
