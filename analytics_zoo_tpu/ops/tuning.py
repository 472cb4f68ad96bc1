"""Block-schedule autotuner for the pallas kernel tier.

The fused int8 kernels and the flash-attention kernels are all parameterized
by a tile schedule — (block_m, block_n, block_k) for the matmul, (block_q,
block_k) for attention.  The right schedule depends on shape AND device: the
fixed constants that earn MFU 0.53 at batch 4 leave the MXU idle at batch 16
(VMEM pressure), and the int8 tiles that win on a v5e are not the v6e ones.

This module sweeps a small candidate grid per (shape-bucket, dtype), scores
each candidate with a **timed probe** plus the **compiled memory analysis**
(structured ``compiled.memory_analysis()`` when the backend provides it,
else the text parser — both in ``analysis.memory`` since ISSUE 12), and
persists the winner in an on-disk JSON cache keyed by device kind, so every
later
process — ``InferenceModel.quantize_int8`` dispatch, ``flash_attention``
call sites, the MFU bench — traces with tuned blocks instead of constants.

Cache location: ``ZOO_TPU_TUNING_CACHE`` env, else ``.zoo_tuning.json`` at
the root of the checkout, beside the compile cache — the block sizes a
program compiles with never depend on a file outside it.  Schema (see
docs/programming-guide/kernels.md)::

    {"version": 1,
     "devices": {"<device_kind>": {
        "int8_matmul": {"<Mbucket>x<N>x<K>/<dtype>":
            {"block_m": 256, "block_n": 256, "block_k": 512,
             "elapsed_ms": 0.41, "hbm": {...}, "swept": [...]}},
        "flash": {"<Tq>x<Tk>/<dtype>":
            {"block_q": 512, "block_k": 512, ...}}}}}

Lookups are in-memory after the first read; ``invalidate()`` drops the
memo (tests, or after an external process re-tuned).  Telemetry:
``zoo_kernel_tuning_sweeps_total`` and the cache hit/miss counters.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis.memory import memory_fields  # noqa: F401  (re-export: the
# structured/text ingestion migrated to the analysis subsystem in ISSUE 12 —
# library code must not import from the bench script; existing callers and
# the tuning-cache schema keep using tuning.memory_fields)
from ..common import telemetry as _tm
from ..common.compile_cache import CHECKOUT
from .backend import interpret_default

_SWEEPS = _tm.counter("zoo_kernel_tuning_sweeps_total",
                      "Autotuner candidate sweeps executed (one per "
                      "(op, shape-bucket, dtype) tuned this process)",
                      labels=("op",))
_HITS = _tm.counter("zoo_kernel_tuning_cache_hits_total",
                    "Kernel-schedule lookups answered from the tuning cache",
                    labels=("op",))
_MISSES = _tm.counter("zoo_kernel_tuning_cache_misses_total",
                      "Kernel-schedule lookups that fell back to the fixed "
                      "default blocks (shape/device never tuned)",
                      labels=("op",))

_CACHE_VERSION = 1
_memo: Dict[str, Optional[dict]] = {}     # path -> parsed cache (None = bad)


def cache_path() -> str:
    return os.environ.get("ZOO_TPU_TUNING_CACHE",
                          os.path.join(CHECKOUT, ".zoo_tuning.json"))


def device_kind() -> str:
    """Cache key: device kind of the default backend (e.g. ``TPU v5e``),
    ``cpu-interpret`` for interpreter-mode runs — schedules never leak
    across device generations."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return f"{dev.platform}-interpret"
    return str(getattr(dev, "device_kind", dev.platform))


def invalidate() -> None:
    """Drop the in-memory cache memo (tests; external re-tune)."""
    _memo.clear()


def _load(path: str) -> dict:
    cached = _memo.get(path)
    if cached is not None:
        return cached
    data: dict = {"version": _CACHE_VERSION, "devices": {}}
    try:
        with open(path) as f:
            raw = json.load(f)
        if isinstance(raw, dict) and raw.get("version") == _CACHE_VERSION:
            data = raw
    except (OSError, ValueError):
        pass
    _memo[path] = data
    return data


def _store(path: str, data: dict) -> None:
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)        # atomic: a killed sweep can't corrupt
    except OSError:
        pass                         # cache is an optimization, never a fault
    _memo[path] = data


def bucket(n: int) -> int:
    """Power-of-two shape bucket (same ladder the serving batcher pads to,
    so one tuned entry covers every batch the bucket admits)."""
    return 1 << (int(n) - 1).bit_length() if n > 1 else 1


def shape_key(*dims: int, dtype=None) -> str:
    key = "x".join(str(int(d)) for d in dims)
    return f"{key}/{np.dtype(dtype).name}" if dtype is not None else key


def lookup(op: str, key: str) -> Optional[dict]:
    """Tuned entry for (device kind, op, key), or None. Counts hit/miss."""
    entry = (_load(cache_path()).get("devices", {})
             .get(device_kind(), {}).get(op, {}).get(key))
    (_HITS if entry else _MISSES).labels(op=op).inc()
    return entry


def record(op: str, key: str, entry: dict) -> None:
    path = cache_path()
    # read-modify-write against the CURRENT file, not the process-lifetime
    # memo: another process may have persisted winners since our first read,
    # and rewriting from a stale snapshot would silently drop them
    _memo.pop(path, None)
    data = _load(path)
    data.setdefault("devices", {}).setdefault(
        device_kind(), {}).setdefault(op, {})[key] = entry
    _store(path, data)


def _time_probe(fn, *args, iters: int = 3, inner: int = 5) -> float:
    """Median wall time of ``inner`` chained dispatches (ms per call)."""
    import jax

    out = fn(*args)                          # compile + warm
    jax.block_until_ready(out)
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / inner * 1e3)
    return float(np.median(samples))


# ------------------------------------------------------------ int8 matmul op

MATMUL_OP = "int8_matmul"

#: Candidate tiles the sweep explores (clamped/shrunk per shape by
#: ``int8_fused.resolve_blocks``). Kept small: each candidate costs a compile.
MATMUL_CANDIDATES: Sequence[Tuple[int, int, int]] = (
    (128, 128, 512), (128, 256, 512), (256, 128, 512),
    (256, 256, 256), (256, 256, 512), (256, 512, 512),
    (512, 256, 512), (512, 512, 256),
)


def matmul_key(m: int, n: int, k: int, dtype) -> str:
    return shape_key(bucket(m), n, k, dtype=dtype)


def matmul_lookup(m: int, n: int, k: int,
                  dtype) -> Optional[Tuple[int, int, int]]:
    """Tuned (block_m, block_n, block_k) for an (M,K)×(K,N) fused int8
    matmul at this shape bucket, or None (callers keep the defaults)."""
    entry = lookup(MATMUL_OP, matmul_key(m, n, k, dtype))
    if not entry:
        return None
    try:
        return int(entry["block_m"]), int(entry["block_n"]), int(entry["block_k"])
    except (KeyError, TypeError, ValueError):
        return None


def tune_int8_matmul(m: int, n: int, k: int, dtype=np.float32, *,
                     candidates: Optional[Sequence[Tuple[int, int, int]]]
                     = None, interpret: Optional[bool] = None,
                     iters: int = 3) -> Optional[dict]:
    """Sweep the candidate tile grid for one (shape-bucket, dtype), score by
    timed probe + compiled memory analysis, persist and return the winner."""
    import jax
    import jax.numpy as jnp

    from . import int8_fused
    from .int8 import quantize_weight

    if interpret is None:
        interpret = interpret_default()
    mb = bucket(m)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(mb, k)), dtype)
    packed = quantize_weight(rng.normal(size=(k, n)).astype(np.float32))
    packed = {"q": jnp.asarray(packed["q"]),
              "scale": jnp.asarray(packed["scale"])}
    _SWEEPS.labels(op=MATMUL_OP).inc()
    swept: List[dict] = []
    seen = set()
    for cand in (candidates or MATMUL_CANDIDATES):
        blocks = int8_fused.resolve_blocks(mb, n, k, dtype, *cand,
                                           interpret=interpret)
        if blocks is None or blocks in seen:
            continue
        seen.add(blocks)
        bm, bn, bk = blocks

        def run(xx, pq=packed["q"], ps=packed["scale"], bm=bm, bn=bn, bk=bk):
            return int8_fused.int8_matmul_fused(
                xx, {"q": pq, "scale": ps}, block_m=bm, block_n=bn,
                block_k=bk, interpret=interpret)

        entry = {"block_m": bm, "block_n": bn, "block_k": bk}
        try:
            jitted = jax.jit(run)
            try:
                entry["hbm"] = memory_fields(jitted.lower(x).compile())
            except Exception:
                entry["hbm"] = {}
            entry["elapsed_ms"] = round(
                _time_probe(jitted, x, iters=iters), 4)
        except Exception as e:   # candidate doesn't compile/fit: skip it
            entry["error"] = str(e)[:200]
            swept.append(entry)
            continue
        swept.append(entry)
    timed = [e for e in swept if "elapsed_ms" in e]
    if not timed:
        return None
    best = dict(min(timed, key=lambda e: e["elapsed_ms"]))
    best["swept"] = swept
    record(MATMUL_OP, matmul_key(m, n, k, dtype), best)
    return best


# ------------------------------------------------------------------- flash op

FLASH_OP = "flash"

FLASH_CANDIDATES: Sequence[Tuple[int, int]] = (
    (128, 128), (256, 128), (256, 256), (512, 256), (512, 512),
)


def flash_key(t_q: int, t_k: int, dtype) -> str:
    return shape_key(t_q, t_k, dtype=dtype)


def flash_lookup(t_q: Optional[int], t_k: Optional[int],
                 dtype=np.dtype("bfloat16")) -> Optional[Tuple[int, int]]:
    """Tuned (block_q, block_k) for a (T_q, T_k) flash attention call, or
    None. Consulted by ``flash_attention.default_blocks`` after the env
    knobs and before the adaptive pow2 heuristic."""
    if not t_q or not t_k:
        return None
    entry = lookup(FLASH_OP, flash_key(t_q, t_k, dtype))
    if not entry:
        return None
    try:
        return int(entry["block_q"]), int(entry["block_k"])
    except (KeyError, TypeError, ValueError):
        return None


def tune_flash_blocks(t_q: int, t_k: int, *, batch: int = 1, heads: int = 8,
                      d: int = 128, dtype=np.dtype("bfloat16"),
                      causal: bool = True, with_backward: bool = True,
                      candidates: Optional[Sequence[Tuple[int, int]]] = None,
                      interpret: Optional[bool] = None,
                      iters: int = 3) -> Optional[dict]:
    """Sweep flash (block_q, block_k) tiles at one sequence shape (fwd+bwd —
    the training MFU regime), persist and return the winner."""
    import jax
    import jax.numpy as jnp

    from .flash_attention import flash_attention

    if interpret is None:
        interpret = interpret_default()
    rng = np.random.default_rng(0)

    def make(shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    q = make((batch, t_q, heads, d))
    k = make((batch, t_k, heads, d))
    v = make((batch, t_k, heads, d))
    _SWEEPS.labels(op=FLASH_OP).inc()
    swept: List[dict] = []
    for bq, bk in (candidates or FLASH_CANDIDATES):
        if t_q % bq or t_k % bk:
            continue
        if with_backward:
            def run(q, k, v, bq=bq, bk=bk):
                return jax.grad(lambda q_, k_, v_: flash_attention(
                    q_, k_, v_, causal, bq, bk, interpret)
                    .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
        else:
            def run(q, k, v, bq=bq, bk=bk):
                return flash_attention(q, k, v, causal, bq, bk, interpret)
        entry = {"block_q": bq, "block_k": bk}
        try:
            jitted = jax.jit(run)
            try:
                entry["hbm"] = memory_fields(jitted.lower(q, k, v).compile())
            except Exception:
                entry["hbm"] = {}
            entry["elapsed_ms"] = round(
                _time_probe(jitted, q, k, v, iters=iters), 4)
        except Exception as e:
            entry["error"] = str(e)[:200]
            swept.append(entry)
            continue
        swept.append(entry)
    timed = [e for e in swept if "elapsed_ms" in e]
    if not timed:
        return None
    best = dict(min(timed, key=lambda e: e["elapsed_ms"]))
    best["swept"] = swept
    best["with_backward"] = with_backward
    record(FLASH_OP, flash_key(t_q, t_k, dtype), best)
    return best


# ------------------------------------------------------------------ paged op

PAGED_OP = "paged"

#: Heads-per-program candidates for the fused paged-attention kernel
#: (filtered to divisors of the model's head count per sweep).
PAGED_CANDIDATES: Sequence[int] = (1, 2, 4, 8, 16)


def paged_key(q_len: int, pages_per_slot: int, page_size: int, h: int,
              d: int, dtype) -> str:
    # "/walk": swept against the kernel that walks a slot's own pages in
    # compute blocks (ISSUE 27). An entry without the suffix was timed on
    # the kernel whose grid ran over every table entry and is never read
    return shape_key(q_len, pages_per_slot, page_size, h, d,
                     dtype=dtype) + "/walk"


def paged_lookup(q_len: int, pages_per_slot: int, page_size: int, h: int,
                 d: int, dtype) -> Optional[int]:
    """Tuned ``block_h`` for a paged-attention call at this cache geometry,
    or None (callers keep the all-heads default). Consulted by
    ``paged_attention.default_block_h`` after the env knob."""
    entry = lookup(PAGED_OP, paged_key(q_len, pages_per_slot, page_size,
                                       h, d, dtype))
    if not entry:
        return None
    try:
        return int(entry["block_h"])
    except (KeyError, TypeError, ValueError):
        return None


def tune_paged_attention(q_len: int, pages_per_slot: int, page_size: int,
                         h: int, d: int, dtype=np.float32, *,
                         n_slots: int = 8,
                         candidates: Optional[Sequence[int]] = None,
                         interpret: Optional[bool] = None,
                         iters: int = 3) -> Optional[dict]:
    """Sweep ``block_h`` for the fused paged-attention kernel at one cache
    geometry (the decode/verify serving regime: B = n_slots, half-full
    slots), persist and return the winner — the decode twin of
    :func:`tune_flash_blocks`."""
    import jax

    from . import paged_attention as pa

    if interpret is None:
        interpret = interpret_default()
    q, k_pages, v_pages, table, lengths = pa.synthetic_paged_case(
        n_slots, pages_per_slot, page_size, h, d, q_len=q_len, dtype=dtype)
    _SWEEPS.labels(op=PAGED_OP).inc()
    swept: List[dict] = []
    for bh in (candidates or PAGED_CANDIDATES):
        if bh > h or h % bh:
            continue

        def run(qq, kk, vv, bh=bh):
            return pa.paged_attention(qq, kk, vv, table, lengths,
                                      page_size=page_size, block_h=bh,
                                      interpret=interpret)

        entry = {"block_h": bh}
        try:
            jitted = jax.jit(run)
            try:
                entry["hbm"] = memory_fields(
                    jitted.lower(q, k_pages, v_pages).compile())
            except Exception:
                entry["hbm"] = {}
            entry["elapsed_ms"] = round(
                _time_probe(jitted, q, k_pages, v_pages, iters=iters), 4)
        except Exception as e:   # candidate doesn't compile/fit: skip it
            entry["error"] = str(e)[:200]
            swept.append(entry)
            continue
        swept.append(entry)
    timed = [e for e in swept if "elapsed_ms" in e]
    if not timed:
        return None
    best = dict(min(timed, key=lambda e: e["elapsed_ms"]))
    best["swept"] = swept
    record(PAGED_OP, paged_key(q_len, pages_per_slot, page_size, h, d,
                               dtype), best)
    return best
