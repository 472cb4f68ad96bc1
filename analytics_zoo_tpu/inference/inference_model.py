"""InferenceModel — multi-backend, concurrency-bounded predictor.

Parity: /root/reference/zoo/src/main/scala/com/intel/analytics/zoo/pipeline/
inference/InferenceModel.scala:33-499 — the reference keeps a
``LinkedBlockingQueue`` pool of model replicas (default ``concurrentNum=20``),
borrows one per ``doPredict`` call, and auto-scales by cloning on demand; loaders
cover BigDL/Caffe/OpenVINO/TF/PyTorch formats.

TPU-native design
-----------------
* One set of weights lives in device HBM; XLA executables are reentrant, so
  "replicas" collapse to a single compiled program guarded by a semaphore that
  reproduces the reference's bounded-concurrency semantics (and its pool
  metrics) without duplicating memory.
* ``jit`` specialises on shape. To keep latency predictable under ragged request
  sizes, inputs are padded up to a small ladder of batch buckets (1,2,4,...,
  ``max_batch``) so at most ``log2(max_batch)+1`` executables ever compile;
  outputs are sliced back. This replaces the reference's per-replica TF/OpenVINO
  sessions with AOT-warmed XLA programs.
* The OpenVINO-Int8 capability (InferenceModel.doLoadOpenVINOInt8) maps to
  REAL int8 compute for native modules: Dense / Convolution2D kernels pack to
  per-channel int8 and the forward runs on the MXU's int8 path with dynamic
  activation quantization (ops/int8.py) — the "up to 2×" speedup property,
  not just the 4× size cut. Imported graphs (load_fn/TF) fall back to
  weight-only packing with on-the-fly dequantization (HBM footprint /4;
  bandwidth-bound layers speed up).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import memwitness as _mw
from ..common import telemetry as _tm
from ..common.locks import traced_lock
from .summary import InferenceSummary, timing

_COMPILES = _tm.counter("zoo_infer_compiles_total",
                        "Bucketed executables built by InferenceModel "
                        "(flat under steady traffic = no mid-stream "
                        "recompiles)")
_CACHE_HITS = _tm.counter("zoo_infer_cache_hits_total",
                          "Dispatches served by a compiled-cache dict lookup")


def _buckets(max_batch: int) -> List[int]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


def _pad_to(arr: np.ndarray, n: int) -> np.ndarray:
    if arr.shape[0] == n:
        return arr
    pad = [(0, n - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad)


@jax.jit
def _scatter_rows(table, idx, rows):
    # specializes per (leaf aval, touched-row count) — the count varies per
    # publish, but embedding deltas dominate and the scatter itself is tiny
    return table.at[idx].set(rows)


def _quantize_leaf(w: np.ndarray) -> Dict[str, np.ndarray]:
    """Per-output-channel symmetric int8 (channels = last dim)."""
    scale = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
    scale = np.maximum(scale, 1e-8) / 127.0
    q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
    return {"q": q, "scale": scale.astype(np.float32)}


def _quantize_module_params(module, params, min_elements: int):
    """Pack the int8-computable kernels of a native module tree; returns
    ``(packed_params, n_packed)``.

    Only layers whose forward actually implements the int8 path are packed —
    the check is the UNOVERRIDDEN ``apply`` (a subclass with its own forward,
    e.g. an atrous variant, would crash on a packed kernel and stays float).
    """
    from ..nn.layers.convolution import Convolution2D
    from ..nn.layers.core import Dense
    from ..ops.int8 import quantize_weight

    int8_applies = (Dense.apply, Convolution2D.apply)
    out = dict(params)
    n_packed = 0
    for layer in getattr(module, "layers", ()) or ():
        slot = module.slot(layer) if hasattr(module, "slot") else None
        p = out.get(slot)
        if p is None:
            continue
        if hasattr(layer, "layers") and hasattr(layer, "slot"):
            out[slot], n = _quantize_module_params(layer, p, min_elements)
            n_packed += n
            continue
        if type(layer).apply not in int8_applies or "kernel" not in p:
            continue
        kernel = np.asarray(p["kernel"])
        if kernel.ndim >= 2 and kernel.size >= min_elements and \
                np.issubdtype(kernel.dtype, np.floating):
            q = dict(p)
            q["kernel"] = quantize_weight(kernel, axis=-1)
            out[slot] = q
            n_packed += 1
    return out, n_packed


class InferenceModel:
    """Bounded-concurrency predictor over a jit-compiled forward.

    Usage::

        im = InferenceModel(supported_concurrent_num=4)
        im.load_zoo("/path/to/bundle")     # .analytics-zoo-style dir bundle
        out = im.predict(np.array(...))    # thread-safe

    ``load(module, params, state)`` accepts any live module (e.g. a fitted
    ``Sequential``/``Model``/zoo model) directly.
    """

    def __init__(self, supported_concurrent_num: int = 20,
                 max_batch_size: int = 1024,
                 summary: Optional[InferenceSummary] = None):
        if supported_concurrent_num < 1:
            raise ValueError("supported_concurrent_num must be >= 1")
        self.concurrent_num = supported_concurrent_num
        self.max_batch_size = max_batch_size
        self._sem = threading.Semaphore(supported_concurrent_num)
        self._lock = traced_lock("InferenceModel._lock")
        self._apply = None          # (params, state, x) -> y
        self._params = None
        self._state = None
        self._compiled: Dict[Tuple, Any] = {}
        self._quantized = False
        self.summary = summary
        # hot-swap support (serving/hotswap.py): the load-time param-tree
        # template — treedef + per-leaf (shape, dtype-name) + signature of
        # the UNQUANTIZED params — is what a published checkpoint is
        # validated against; `version` tags every response this model serves
        self.version: Optional[str] = None
        self.load_treedef = None
        self.load_avals: Optional[List[Tuple[Tuple, str]]] = None
        self.load_signature: Optional[str] = None
        self._plain_apply = None    # pre-quantization apply (swap/requantize)
        self._quant_min_elements: Optional[int] = None
        # per-thread version snapshot taken INSIDE the concurrency slot
        # (while any slot is held a swap cannot flip params, so this is
        # exactly the version whose weights served that thread's last
        # predict — the attribution a post-predict read would race)
        self._served_version: Dict[int, Optional[str]] = {}
        # pool metrics (InferenceModel.scala keeps originalModel + clones count)
        self.borrowed_peak = 0
        self._borrowed = 0
        # bucket-cache accounting: ``compiles`` counts executables built (one
        # per distinct bucketed shape — flat under steady traffic = XLA never
        # recompiles mid-stream), ``cache_hits`` counts dict-lookup dispatches
        self.compile_count = 0
        self.cache_hit_count = 0
        # int8 packing wall time (quantize_int8) — startup cost the serving
        # engine pays at warmup instead of the first request
        self.quantize_seconds = 0.0
        # recompilation-hazard tracker: the bucket ladder promises at most
        # log2(max_batch)+1 executables per feature shape; a dispatch-key set
        # outgrowing 2x that bound means this model compiles under live
        # traffic (analysis/ graph-lint "recompile-hazard", flagged once)
        from ..analysis.graphlint import SignatureTracker

        self._sig_tracker = SignatureTracker.for_bucket_ladder(
            "inference.predict", max_batch_size, shapes_per_bucket=2)

    # ------------------------------------------------------------------ loading

    def load(self, module, params=None, state=None) -> "InferenceModel":
        """Load from a live module. If ``module`` is a compiled KerasNet/zoo
        model with trained state, params/state default to it."""
        if params is None:
            est = getattr(module, "estimator", None)
            if est is not None and est.train_state is not None:
                params = est.train_state["params"]
                state = est.train_state["model_state"]
            elif est is not None and getattr(est, "initial_weights", None):
                params, state = est.initial_weights
            else:
                raise ValueError("module has no trained state; pass params=")
        self._apply = lambda p, s, x, m=module: m.apply(p, s, x, training=False)[0]
        self._module = module
        self._params = jax.device_put(params)
        self._state = jax.device_put(state if state is not None else {})
        self._compiled.clear()
        self._record_template(params)
        return self

    def load_zoo(self, path: str, model_class=None) -> "InferenceModel":
        """Load a ``.analytics-zoo``-style directory bundle saved by
        ``ZooModel.save_model`` (InferenceModel.doLoadBigDL parity: rebuild
        architecture + weights, ready to predict)."""
        from ..models.common.zoo_model import load_model_bundle

        model, _cfg = load_model_bundle(path, model=None if model_class is None
                                        else model_class())
        # Bundle restore defers weights to compile; force materialisation now.
        if getattr(model, "estimator", None) is None:
            model.compile(optimizer="sgd", loss="mse")
        return self.load(model)

    def load_tf(self, path: str, signature: str = "serving_default",
                inputs=None, outputs=None) -> "InferenceModel":
        """Load a TF frozen graph (``.pb``) or SavedModel dir and serve it
        (InferenceModel.doLoadTF parity, InferenceModel.scala:83-300 — the
        reference embeds libtensorflow; here the graph executes as a traced
        jnp program via importers.tf_net)."""
        import os

        from ..importers.tf_net import from_frozen_graph, from_saved_model

        if os.path.isdir(path):
            net = from_saved_model(path, signature=signature, inputs=inputs,
                                   outputs=outputs)
        else:
            net = from_frozen_graph(path, inputs=inputs, outputs=outputs)

        # SavedModel variables ride the params pytree so quantize_int8 applies
        # to them; frozen-graph weights are Const nodes inside the traced
        # program and stay full-precision (params is empty then)
        def apply(p, s, x, net=net):
            xs = list(x) if isinstance(x, (list, tuple)) else [x]
            return net._run(*xs, variables=p)

        return self.load_fn(apply, params=dict(net.variables), state=None)

    def load_fn(self, fn, params, state=None) -> "InferenceModel":
        """Load a bare ``fn(params, state, x) -> y`` (escape hatch for imported
        graphs — the TFNet/TorchNet capability lands here via importers)."""
        self._apply = fn
        self._module = None
        self._params = jax.device_put(params)
        self._state = jax.device_put(state if state is not None else {})
        self._compiled.clear()
        self._record_template(params)
        return self

    def _record_template(self, params) -> None:
        """Remember the as-loaded (unquantized) param-tree shape: treedef +
        per-leaf avals + signature. The hot-swap staging path validates a
        published checkpoint against this BEFORE touching live params —
        equal signature ⇒ same avals ⇒ the live executables keep serving
        the new weights without a recompile."""
        from ..engine.checkpoint import param_tree_signature

        leaves, treedef = jax.tree_util.tree_flatten(params)
        self._plain_apply = self._apply
        self.load_treedef = treedef
        self.load_avals = [
            (tuple(np.shape(l)),
             np.dtype(getattr(l, "dtype", np.asarray(l).dtype)).name)
            for l in leaves]
        self.load_signature = param_tree_signature(leaves)
        self.version = None

    # ------------------------------------------------------------- quantization

    def quantize_int8(self, min_elements: int = 4096) -> "InferenceModel":
        """Int8 quantization (InferenceModel.doLoadOpenVINOInt8 capability,
        OpenVinoInferenceSupportive.scala:32-55 / wp-bigdl.md:192).

        Native modules: Dense / Convolution2D kernels >= ``min_elements`` pack
        to per-output-channel int8 and the forward COMPUTES in int8 on the MXU
        (dynamic activation quantization fused into the pallas kernel tier on
        TPU; lax fallback elsewhere — ops/int8.py router). Imported-graph
        loads (no module): weight-only packing, dequantized inside the
        compiled program (size cut only).

        The packing cost is timed into ``compile_stats()['quantize_seconds']``
        so callers (the serving engine's startup warmup) can account for it
        off the first-request path.
        """
        if self._params is None:
            raise RuntimeError("load a model before quantizing")
        t0 = time.perf_counter()
        self._quant_min_elements = min_elements
        host_params = jax.device_get(self._params)
        new_apply, packed = self._build_quantized(host_params, min_elements)
        self._apply = new_apply
        self._params = jax.device_put(packed)
        self._compiled.clear()
        self._quantized = True
        self.quantize_seconds += time.perf_counter() - t0
        return self

    def _build_quantized(self, host_params, min_elements: int):
        """Pack ``host_params`` (an UNQUANTIZED host tree in the load-time
        layout) for int8 serving; returns ``(apply_fn, packed_host_params)``.
        Shared by :meth:`quantize_int8` and the hot-swap requantize path —
        the swap flips apply+params as one consistent pair."""
        module = getattr(self, "_module", None)
        if module is not None and hasattr(module, "layers"):
            packed_params, n_native = _quantize_module_params(
                module, host_params, min_elements)
            if n_native:
                return self._plain_apply, packed_params
            # no int8-computable layer (LSTM/embedding/custom models): fall
            # through to the generic weight-only path so the 4x size cut —
            # the minimum doLoadOpenVINOInt8 property — still happens

        flat, treedef = jax.tree_util.tree_flatten(host_params)
        packed = []
        for leaf in flat:
            arr = np.asarray(jax.device_get(leaf))
            if arr.ndim >= 2 and arr.size >= min_elements and \
                    np.issubdtype(arr.dtype, np.floating):
                packed.append(_quantize_leaf(arr))
            else:
                packed.append(arr)
        # wrap the PLAIN apply (not the current one): requantizing after a
        # swap must not stack a second dequant layer
        inner_apply = self._plain_apply

        def dequant(p):
            flat_q, td = jax.tree_util.tree_flatten(
                p, is_leaf=lambda x: isinstance(x, dict) and "q" in x)
            deq = [x["q"].astype(jnp.float32) * x["scale"]
                   if isinstance(x, dict) and "q" in x else x for x in flat_q]
            return jax.tree_util.tree_unflatten(td, deq)

        apply_fn = lambda p, s, x: inner_apply(dequant(p), s, x)  # noqa: E731
        return apply_fn, jax.tree_util.tree_unflatten(treedef, packed)

    # ----------------------------------------------------------------- hot-swap

    def host_params(self):
        """The live params as a HOST tree in the load-time (unquantized)
        layout — the rollback retention snapshot. For a quantized model the
        packed int8 kernels are dequantized back to float host-side; the
        re-quantize on rollback reproduces the same packed values (the
        round trip is idempotent for already-quantized weights)."""
        if self._params is None:
            raise RuntimeError("no model loaded")
        host = jax.device_get(self._params)
        if not self._quantized:
            return host

        def deq(x):
            if isinstance(x, dict) and "q" in x and "scale" in x:
                return np.asarray(x["q"], np.float32) * np.asarray(x["scale"])
            return x

        flat, td = jax.tree_util.tree_flatten(
            host, is_leaf=lambda x: isinstance(x, dict) and "q" in x)
        return jax.tree_util.tree_unflatten(td, [deq(x) for x in flat])

    def probe_forward(self, params, x):
        """Run the load-time forward with CANDIDATE params (host or device
        tree, unquantized layout) WITHOUT touching live state — the hot-swap
        warmup probe. Uses the plain apply: quantized packing happens only
        at swap time, after the probe passed. The caller owns the device
        placement (the swapper stages one device copy and reuses it for the
        flip)."""
        if self._plain_apply is None:
            raise RuntimeError("no load-time template (use load/load_fn)")
        return self._plain_apply(params, self._state, jnp.asarray(x))

    def _hold_all_slots(self):
        """Acquire every concurrency slot — nothing is mid-``predict`` while
        held, so a reference flip inside lands exactly BETWEEN dispatch
        waves and no in-flight request can see mixed weights."""
        import contextlib

        @contextlib.contextmanager
        def gate():
            for _ in range(self.concurrent_num):
                self._sem.acquire()
            try:
                yield
            finally:
                for _ in range(self.concurrent_num):
                    self._sem.release()

        return gate()

    def swap_params(self, params, version: Optional[str] = None
                    ) -> "InferenceModel":
        """Atomically replace the live params with ``params`` (a host tree
        in the load-time layout, e.g. staged from a published checkpoint).

        All expensive work — device transfer, int8 re-packing for a
        quantized model — happens BEFORE the gate; the flip itself holds
        every concurrency slot so it lands between dispatch waves. Equal
        avals (enforced by the staging validation) mean the compiled
        executables keep serving: for an unquantized model the cache
        survives untouched (params are call arguments, not captures); a
        quantized model re-packs, and its apply+params+cache flip as one
        consistent set."""
        if self._plain_apply is None:
            raise RuntimeError("swap_params needs a load-time template "
                               "(use load/load_fn)")
        if self._quantized:
            new_apply, packed = self._build_quantized(
                params, self._quant_min_elements or 4096)
            new_params = jax.device_put(packed)
        else:
            new_apply = self._plain_apply
            new_params = jax.device_put(params)
        # same apply identity (unquantized, or module-path int8 packing) ⇒
        # the compiled cache stays valid: params are call arguments, and the
        # staging validation guaranteed equal avals. A fresh generic-path
        # dequant wrapper must drop the cache with the flip.
        clear = new_apply is not self._apply
        with self._hold_all_slots():
            self._apply = new_apply
            self._params = new_params
            if clear:
                self._compiled.clear()
            self.version = version
        return self

    def apply_row_delta(self, entries, *, version: Optional[str] = None
                        ) -> "InferenceModel":
        """Patch the live params IN PLACE from a row-delta publish: scatter
        only the touched rows into each affected leaf instead of staging a
        full replacement tree. ``entries`` is ``[(leaf_index, idx, rows)]``
        in the load-time flatten order — ``idx=None`` means ``rows`` is a
        whole-leaf replacement (the delta's dense fallback).

        Only the touched rows cross host→device; each patched leaf keeps its
        aval, so the compiled executables keep serving with zero recompiles
        (params are call arguments, not captures). The scatter runs on an
        undonated copy — the pre-flip leaf may still be mid-``predict`` on
        another slot, so its buffer must stay valid until the gated flip.
        Quantized models reject the patch: rows can't be scattered into
        int8-packed kernels, so they take the full-checkpoint path."""
        if self._plain_apply is None:
            raise RuntimeError("apply_row_delta needs a load-time template "
                               "(use load/load_fn)")
        if self._quantized:
            raise RuntimeError(
                "row deltas cannot patch int8-packed params — publish a "
                "full checkpoint for quantized serving")
        if self._params is None:
            raise RuntimeError("no model loaded")
        leaves, treedef = jax.tree_util.tree_flatten(self._params)
        for leaf_idx, idx, rows in entries:
            cur = leaves[leaf_idx]
            if idx is None:
                leaves[leaf_idx] = jax.device_put(
                    jnp.asarray(rows, cur.dtype))
            else:
                leaves[leaf_idx] = _scatter_rows(
                    cur, jnp.asarray(np.asarray(idx, np.int32)),
                    jnp.asarray(rows, cur.dtype))
        new_params = jax.tree_util.tree_unflatten(treedef, leaves)
        with self._hold_all_slots():
            self._params = new_params
            if version is not None:
                self.version = version
        return self

    # ---------------------------------------------------------------- predicting

    def _executable(self, key: Tuple):
        exe = self._compiled.get(key)
        if exe is None:
            with self._lock:
                exe = self._compiled.get(key)
                if exe is None:
                    exe = jax.jit(self._apply)
                    self._compiled[key] = exe
                    self.compile_count += 1
                    _COMPILES.inc()
                    self._sig_tracker.add(key)
                    return exe
        self.cache_hit_count += 1
        _CACHE_HITS.inc()
        return exe

    def compile_stats(self) -> Dict[str, Any]:
        """Bucket-cache counters (surfaced at /metrics and by the bench):
        ``compiled_shapes``/``compiles`` bound by the bucket ladder,
        ``cache_hits`` = dispatches served by a dict lookup,
        ``quantize_seconds`` = int8 packing wall time (0.0 unquantized)."""
        return {"compiled_shapes": len(self._compiled),
                "compiles": self.compile_count,
                "cache_hits": self.cache_hit_count,
                "quantize_seconds": round(self.quantize_seconds, 4)}

    def _bucket(self, n: int) -> int:
        for b in _buckets(self.max_batch_size):
            if n <= b:
                return b
        return self.max_batch_size

    def _validate_inputs(self, inputs):
        if self._apply is None:
            raise RuntimeError("no model loaded (call load/load_zoo first)")
        multi = isinstance(inputs, (list, tuple))
        arrs = [np.asarray(a) for a in (inputs if multi else [inputs])]
        n = arrs[0].shape[0]
        if any(a.shape[0] != n for a in arrs):
            raise ValueError("all inputs must share the batch dimension")
        return arrs, multi, n

    def _dispatch_chunks(self, arrs, multi, n):
        """Pad each ≤max_batch chunk to its bucket and ENQUEUE the executable
        — returns ``[(device_result, valid_count), ...]`` without waiting.
        JAX dispatch is asynchronous, so the device starts working
        immediately; only fetching blocks."""
        dispatched = []
        for lo in range(0, n, self.max_batch_size):
            hi = min(lo + self.max_batch_size, n)
            bucket = self._bucket(hi - lo)
            padded = [_pad_to(a[lo:hi], bucket) for a in arrs]
            x = padded if multi else padded[0]
            key = (bucket,) + tuple((a.shape[1:], str(a.dtype))
                                    for a in padded)
            with timing("inference.forward"):
                y = self._executable(key)(self._params, self._state, x)
            dispatched.append((y, hi - lo))
        return dispatched

    @staticmethod
    def _gather_chunks(dispatched):
        outs = [jax.tree_util.tree_map(
                    lambda a: np.asarray(jax.device_get(a))[:m], y)
                for y, m in dispatched]
        if len(outs) == 1:
            return outs[0]
        return jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *outs)

    def predict(self, inputs, batch_first: bool = True):
        """Thread-safe bounded-concurrency predict (doPredict parity).

        ``inputs``: ndarray or list/tuple of ndarrays (multi-input models).
        Requests larger than ``max_batch_size`` are chunked.
        """
        arrs, multi, n = self._validate_inputs(inputs)
        t0 = time.perf_counter()
        with self._sem:
            with self._lock:
                self._borrowed += 1
                self.borrowed_peak = max(self.borrowed_peak, self._borrowed)
            # slot held ⇒ no swap can be mid-flight: this version IS the one
            # whose params the dispatch below reads
            if len(self._served_version) > 4096:   # dead-thread-id bound
                self._served_version.clear()
            self._served_version[threading.get_ident()] = self.version
            try:
                result = self._gather_chunks(
                    self._dispatch_chunks(arrs, multi, n))
            finally:
                with self._lock:
                    self._borrowed -= 1
        _mw.sample("inference.dispatch")
        if self.summary is not None:
            self.summary.add_batch(n, time.perf_counter() - t0)
        return result

    def last_served_version(self) -> Optional[str]:
        """Version of the params that served THIS thread's last ``predict``
        (None before the first call, or for never-swapped models). Race-free
        w.r.t. concurrent hot-swaps — the snapshot is taken inside the
        concurrency slot."""
        return self._served_version.get(threading.get_ident())

    # ------------------------------------------------------- device-level access

    def device_apply(self):
        """``(apply_fn, params, state)`` — the exact computation ``predict``
        compiles, with params/state already device-resident.

        Public escape hatch for AOT export and for whoever inspects the
        program (the ``fused-int8-dispatch`` rule and ``chip_smoke.py``
        lower it, so what they check cannot decouple from the real predict
        path): after ``quantize_int8`` the returned ``apply_fn``/``params``
        are the quantized ones."""
        if self._apply is None:
            raise RuntimeError("no model loaded (call load/load_zoo first)")
        return self._apply, self._params, self._state

    # ------------------------------------------------------------------- warmup

    def warm_up(self, example_inputs, graph_checks: Optional[str] = None
                ) -> None:
        """Compile the bucket ladder ahead of traffic (AOT; replaces the
        reference's replica-clone prefill). ``graph_checks`` ("warn"/"raise")
        additionally runs :meth:`check_fused_dispatch` so a quantized model
        whose fused kernels are silently not dispatching is caught here —
        at model-load time — instead of at the next bench run."""
        multi = isinstance(example_inputs, (list, tuple))
        arrs = [np.asarray(a) for a in
                (example_inputs if multi else [example_inputs])]
        for b in _buckets(self.max_batch_size):
            padded = [_pad_to(a[:1], b) for a in arrs]
            self.predict(padded if multi else padded[0])
        if graph_checks:
            self.check_fused_dispatch(example_inputs, mode=graph_checks)
            self.check_memory(example_inputs, mode=graph_checks)

    def check_fused_dispatch(self, example_inputs, mode: str = "warn"):
        """Run the ``fused-int8-dispatch`` graph rule over the exact
        computation :meth:`predict` compiles (the PR-6 regression class:
        quantized model, fused tier claimed on, but the jaxpr shows lax
        quantize ops / int8 HBM intermediates instead of pallas kernels).

        No-op unless the model is quantized AND the fused tier is routed on
        (``ops.int8_fused.fused_mode() != "off"``) — an un-quantized or
        deliberately-lax model has no fused invariant to hold. ``mode``:
        "warn" logs findings, "raise" raises
        :class:`analytics_zoo_tpu.analysis.GraphLintError`. Returns the
        findings."""
        from ..analysis import RuleContext, enforce
        from ..analysis.rules.fused_int8 import lint_fused_dispatch
        from ..ops.int8_fused import fused_mode

        if not mode or mode == "off":
            return []
        if not self._quantized or fused_mode() == "off":
            return []
        import logging

        multi = isinstance(example_inputs, (list, tuple))
        arrs = [jnp.asarray(np.asarray(a)[:1]) for a in
                (example_inputs if multi else [example_inputs])]
        x = arrs if multi else arrs[0]
        ctx = RuleContext(where="inference.load", fused_expected=True)
        findings = lint_fused_dispatch(self, x, ctx=ctx)
        return enforce(findings, mode,
                       logging.getLogger("analytics_zoo_tpu.inference"))

    def check_memory(self, example_inputs, mode: str = "warn",
                     budget_bytes: Optional[int] = None):
        """Run the memory tier over the exact computation :meth:`predict`
        compiles: ``hbm-budget`` when ``budget_bytes`` declares a per-device
        budget (``ServingConfig.hbm_budget_mb`` through ``_warm_model``) and
        ``peak-temporary`` always — the static live-range estimate of the
        dispatch, checked at model-load time exactly like the fused-dispatch
        structure. Also notes the static peak into the runtime memory
        witness (site ``inference.dispatch``) when witnessing is on.
        Returns the findings."""
        from ..analysis import RuleContext, enforce, profile_jaxpr
        from ..analysis.rules.fused_int8 import _trace_dispatch
        from ..analysis.rules.memory import lint_memory
        from ..common import memwitness as _mw

        if not mode or mode == "off":
            return []
        import logging

        multi = isinstance(example_inputs, (list, tuple))
        arrs = [jnp.asarray(np.asarray(a)[:1]) for a in
                (example_inputs if multi else [example_inputs])]
        x = arrs if multi else arrs[0]
        closed = _trace_dispatch(self, x)
        ctx = RuleContext(where="inference.load",
                          hbm_budget_bytes=budget_bytes)
        findings = lint_memory(closed, ctx=ctx,
                               rules=["hbm-budget", "peak-temporary"])
        if _mw.enabled():
            prof = profile_jaxpr(closed)
            _mw.note_static("inference.dispatch", prof.peak_live_bytes,
                            budget_bytes)
        return enforce(findings, mode,
                       logging.getLogger("analytics_zoo_tpu.inference"))

    @property
    def is_quantized(self) -> bool:
        return self._quantized

    def __repr__(self):
        return (f"InferenceModel(concurrent_num={self.concurrent_num}, "
                f"loaded={self._apply is not None}, int8={self._quantized})")
