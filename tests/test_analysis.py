"""Graph-lint subsystem tests (ISSUE 7).

Golden-fixture suite: one minimal jitted function (or source snippet) per
shipped rule, each tripping exactly that rule exactly once — so a rule that
goes quiet (or noisy) fails a test, not a bench run. Plus the tier-1
clean-repo gate (the package itself must lint clean), the suppression
syntax, the ``TrainConfig.graph_checks`` fit-time hook (a deliberately
broken ZeRO-1 exchange and a closure-captured weight blob are caught at
``fit()`` start in ``"raise"`` mode), and the model-load-time
fused-dispatch check on ``InferenceModel``/the serving engine warmup.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu import analysis
from analytics_zoo_tpu.analysis import (GraphLintError, RuleContext,
                                        SignatureTracker, lint_hlo,
                                        lint_signatures, lint_source,
                                        lint_traced)

pytestmark = pytest.mark.analysis

PKG_ROOT = os.path.join(os.path.dirname(__file__), "..", "analytics_zoo_tpu")


def _one(findings, rule):
    """Assert the fixture tripped exactly ``rule`` exactly once."""
    assert len(findings) == 1, [str(f) for f in findings]
    assert findings[0].rule == rule, str(findings[0])
    return findings[0]


# ------------------------------------------------------- jaxpr-layer fixtures

def test_golden_collective_budget(devices):
    """psum where the budget demands a reduce-scatter → one finding."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(devices), ("dp",))
    fn = shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                   in_specs=P(), out_specs=P(), check_vma=False)
    ctx = RuleContext(where="fixture",
                      expect_collectives={"reduce-scatter": 1})
    f = _one(lint_traced(fn, jnp.ones((16,)), ctx=ctx,
                         rules=["collective-budget"]), "collective-budget")
    assert dict(f.data)["found"] == 0 and dict(f.data)["expected"] == 1


def test_golden_collective_budget_in_loop(devices):
    """A collective inside the accumulation scan → one finding even though
    the total count matches the budget."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(devices), ("dp",))

    def body(v):
        def step(c, _):
            return c + jax.lax.psum_scatter(v, "dp", scatter_dimension=0,
                                            tiled=True).sum(), None
        out, _ = jax.lax.scan(step, jnp.float32(0), jnp.arange(4))
        return out

    fn = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                   check_vma=False)
    ctx = RuleContext(where="fixture",
                      expect_collectives={"reduce-scatter": 1})
    f = _one(lint_traced(fn, jnp.ones((16,)), ctx=ctx,
                         rules=["collective-budget"]), "collective-budget")
    assert dict(f.data)["in_loop"] == 1


def test_golden_collective_budget_hlo(devices):
    """Compiled-HLO layer: budget mismatch on real post-XLA text."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(devices), ("dp",))
    fn = jax.jit(shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                           in_specs=P(), out_specs=P(), check_vma=False))
    hlo = fn.lower(jnp.ones((16,))).compile().as_text()
    ctx = RuleContext(where="fixture", expect_collectives={"all-reduce": 2})
    f = _one(lint_hlo(hlo, ctx=ctx, rules=["collective-budget-hlo"]),
             "collective-budget-hlo")
    assert dict(f.data)["found"] == 1


def test_golden_fused_int8_dispatch(monkeypatch, np_rng):
    """Fused kernels present but one standalone quantize op alongside →
    exactly the quantize-op invariant trips."""
    monkeypatch.setenv("ZOO_INT8_FUSED", "interpret")
    from analytics_zoo_tpu.ops import int8_fused
    from analytics_zoo_tpu.ops.int8 import quantize_weight

    w = np_rng.normal(size=(32, 32)).astype(np.float32)
    packed = {k: jnp.asarray(v) for k, v in quantize_weight(w).items()}

    def f(x):
        y = int8_fused.int8_matmul_fused(x, packed, interpret=True)
        return jnp.round(y)          # the standalone HBM quantize op

    ctx = RuleContext(where="fixture", fused_expected=True)
    x = jnp.asarray(np_rng.normal(size=(8, 32)).astype(np.float32))
    f = _one(lint_traced(f, x, ctx=ctx, rules=["fused-int8-dispatch"]),
             "fused-int8-dispatch")
    assert dict(f.data)["count"] == 1


def test_golden_host_transfer():
    def f(x):
        jax.debug.callback(lambda v: None, x)
        return x * 2

    f = _one(lint_traced(f, jnp.ones((4,)),
                         ctx=RuleContext(where="fixture"),
                         rules=["host-transfer"]), "host-transfer")
    assert dict(f.data)["primitive"] == "debug_callback"


def test_golden_large_constant():
    big = np.ones((1024, 512), np.float32)          # 2 MiB, closure-captured

    f = _one(lint_traced(lambda x: x @ jnp.asarray(big),
                         jnp.ones((4, 1024)),
                         ctx=RuleContext(where="fixture"),
                         rules=["large-constant"]), "large-constant")
    assert dict(f.data)["nbytes"] == big.nbytes


def test_golden_dtype_discipline():
    ctx = RuleContext(where="fixture", compute_dtype="bfloat16")
    f = _one(lint_traced(lambda a, b: a @ b,
                         jnp.ones((4, 4), jnp.float32),
                         jnp.ones((4, 4), jnp.float32),
                         ctx=ctx, rules=["dtype-discipline"]),
             "dtype-discipline")
    assert dict(f.data)["count"] == 1
    # the same trace under a matching (f32) declaration is clean
    assert lint_traced(lambda a, b: a @ b, jnp.ones((4, 4)), jnp.ones((4, 4)),
                       ctx=RuleContext(where="fixture"),
                       rules=["dtype-discipline"]) == []


def test_golden_recompile_hazard():
    sigs = [((i, 32), "float32") for i in range(5)]
    ctx = RuleContext(where="fixture", max_signatures=4)
    f = _one(lint_signatures(sigs, ctx=ctx, rules=["recompile-hazard"]),
             "recompile-hazard")
    assert dict(f.data) == {"bound": 4, "distinct": 5}
    # the tracker flags once, at the crossing, and not again
    tr = SignatureTracker("fixture", max_distinct=2)
    flags = [tr.add(s) for s in sigs[:4]]
    assert flags == [False, False, True, False]


# --------------------------------------------------------- AST-layer fixtures

def _ast_one(src, rule, **kw):
    findings, _ = lint_source(src, "fixture.py", **kw)
    return _one(findings, rule)


def test_golden_tracer_leak():
    _ast_one(
        "import jax\n"
        "def step(x):\n"
        "    return float(x) + 1\n"
        "jitted = jax.jit(step)\n",
        "tracer-leak")


def test_golden_wallclock_in_jit():
    _ast_one(
        "import jax, time\n"
        "def step(x):\n"
        "    return x * time.time()\n"
        "jitted = jax.jit(step)\n",
        "wallclock-in-jit")


def test_golden_telemetry_lock():
    """The one-off telemetry-lock rule generalized into guarded-by
    inference (ISSUE 11): the registry-shaped fixture now trips
    ``lock-guarded-by``, and the OLD rule name still works as a
    suppression/get_rule alias so pre-migration comments stay valid."""
    src = ("import threading\n"
           "class R:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self._families = {}\n"
           "    def add(self, k, v):\n"
           "        with self._lock:\n"
           "            self._families[k] = v\n"
           "    def drop(self, k):\n"
           "        with self._lock:\n"
           "            self._families.pop(k, None)\n"
           "    def sneak(self, k, v):\n"
           "        self._families[k] = v\n")
    f = _ast_one(src, "lock-guarded-by")
    assert f.location.endswith(":13")
    # the historical name resolves to the successor rule...
    from analytics_zoo_tpu.analysis import get_rule

    assert get_rule("telemetry-lock").id == "lock-guarded-by"
    # ...and historical suppressions still silence it
    suppressed_src = src.replace(
        "    def sneak(self, k, v):\n        self._families[k] = v\n",
        "    def sneak(self, k, v):\n"
        "        # zoo-lint: disable=telemetry-lock — fixture\n"
        "        self._families[k] = v\n")
    findings, n_suppressed = lint_source(suppressed_src, "fixture.py")
    assert findings == [] and n_suppressed == 1


def test_golden_chaos_site():
    _ast_one(
        "from analytics_zoo_tpu.common.chaos import chaos_point\n"
        "def f():\n"
        "    chaos_point('definitely.not.registered')\n",
        "chaos-site")


def test_ast_negative_space():
    """Host-side float(), jax.random, guarded registry writes, registered
    chaos sites: all clean."""
    src = (
        "import jax, time\n"
        "from analytics_zoo_tpu.common.chaos import chaos_point\n"
        "def host(v):\n"
        "    chaos_point('estimator.step')\n"
        "    return float(v), time.time()\n"
        "def step(x, rng):\n"
        "    return x + jax.random.normal(rng, x.shape)\n"
        "jitted = jax.jit(step)\n"
        "class R:\n"
        "    def add(self, k, v):\n"
        "        with self._lock:\n"
        "            self._families[k] = v\n")
    findings, _ = lint_source(src, "fixture.py")
    assert findings == []


def test_ast_nested_def_reports_once():
    """A leak inside a def nested in a traced function is one finding, not
    one per enclosing traced_fns entry."""
    src = ("import jax\n"
           "@jax.jit\n"
           "def outer(x):\n"
           "    def inner(v):\n"
           "        y = v + 1\n"
           "        return float(y)\n"
           "    return inner(x)\n")
    findings, _ = lint_source(src, "fixture.py")
    assert len(findings) == 1 and findings[0].rule == "tracer-leak"


def test_ast_non_function_wrapper_args_not_traced():
    """scan's carry / fori_loop's bounds are values, not functions — a host
    function sharing such a name must not be marked traced."""
    src = ("import time, jax\n"
           "def init():\n"
           "    return time.time()\n"
           "def run(step, xs):\n"
           "    out, _ = jax.lax.scan(step, init, xs)\n"
           "    return out\n")
    findings, _ = lint_source(src, "fixture.py")
    assert findings == []


def test_suppression_inline_and_preceding_line():
    src = ("import jax\n"
           "def step(x):\n"
           "    a = float(x)  # zoo-lint: disable=tracer-leak — fixture\n"
           "    # zoo-lint: disable=tracer-leak — fixture\n"
           "    b = float(x)\n"
           "    c = float(x)\n"
           "    return a + b + c\n"
           "jitted = jax.jit(step)\n")
    findings, suppressed = lint_source(src, "fixture.py")
    assert suppressed == 2
    assert len(findings) == 1 and findings[0].location.endswith(":6")
    # disable=all works too
    src_all = src.replace("disable=tracer-leak — fixture\n    b",
                          "disable=all — fixture\n    b")
    _, suppressed_all = lint_source(src_all, "fixture.py")
    assert suppressed_all == 2


def test_findings_land_in_telemetry():
    from analytics_zoo_tpu.common import telemetry as _tm

    before = _tm.snapshot().get("zoo_analysis_findings_total", {}) \
        .get("samples", {}).get("tracer-leak,error", 0)
    test_golden_tracer_leak()
    after = _tm.snapshot()["zoo_analysis_findings_total"]["samples"][
        "tracer-leak,error"]
    assert after == before + 1


# ------------------------------------------------------------ clean-repo gate

def test_repo_lints_clean():
    """Tier-1 gate: the package carries zero unsuppressed findings (genuine
    bugs get fixed; intentional patterns get justified inline
    suppressions)."""
    findings, _suppressed = analysis.lint_package(PKG_ROOT)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_exit_status(tmp_path):
    from analytics_zoo_tpu.analysis.__main__ import main

    assert main([PKG_ROOT]) == 0
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n"
                   "def step(x):\n"
                   "    return float(x)\n"
                   "jitted = jax.jit(step)\n")
    assert main([str(bad)]) == 1
    assert main(["--list-rules"]) == 0


# ------------------------------------------------- fit-time graph_checks hook

def _toy_fit(graph_checks, loss="mse", **cfg_kw):
    from analytics_zoo_tpu.common import TrainConfig
    from analytics_zoo_tpu.engine import Estimator
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = rng.normal(size=(64, 4)).astype(np.float32)
    model = Sequential([L.Dense(8, activation="relu", input_shape=(16,)),
                        L.Dense(4)])
    est = Estimator(model, optimizer="sgd", loss=loss,
                    config=TrainConfig(shuffle=False,
                                       log_every_n_steps=10 ** 9,
                                       graph_checks=graph_checks, **cfg_kw))
    est.fit((x, y), batch_size=32, epochs=1)
    return est


def test_graph_checks_clean_fit_passes(zoo_ctx):
    est = _toy_fit("raise")
    assert est.trainer_state.iteration == 2


@pytest.fixture(params=[None, 48], ids=["one-bucket", "several-buckets"])
def flat_buckets(request, bucket_target):
    """Run a flat update-sharding test at the default bucket target (the toy
    fits one bucket) and with a 48-element target (the toy's 172 parameters,
    22 rows of 8, then exchange 4 buckets). Returns the count."""
    if request.param is None:
        return 1
    bucket_target(request.param)
    return 4


def test_graph_checks_flat_sharding_passes(zoo_ctx, flat_buckets):
    """The collective budget is one reduce-scatter + one all-gather per
    bucket of the flat meta: a clean exchange passes with one and several."""
    est = _toy_fit("raise", update_sharding=True)
    assert est._update_mode() == "flat"
    assert est._flat_meta.n_buckets == flat_buckets
    assert est.trainer_state.iteration == 2


def test_graph_checks_catch_broken_flat_exchange(zoo_ctx, monkeypatch,
                                                 flat_buckets):
    """Deliberately break the ZeRO-1 exchange (psum instead of the
    reduce-scatter/all-gather pair): graph_checks='raise' fails fit()
    BEFORE the first step compiles, whatever the bucket count."""
    from analytics_zoo_tpu.parallel import update_sharding as upd

    def broken_exchange(params, grads, opt_state, meta, tx, *, axis="dp",
                        clip_norm=None, clip_value=None):
        gflat = upd.flatten_tree(grads, meta, jnp.float32)
        g = jax.lax.psum(gflat, axis)                # the pre-ZeRO-1 shape
        gnorm = jnp.sqrt(jnp.sum(g * g))
        return params, opt_state, gnorm

    monkeypatch.setattr(upd, "flat_exchange", broken_exchange)
    with pytest.raises(GraphLintError,
                       match=f"expected {flat_buckets} reduce-scatter"):
        _toy_fit("raise", update_sharding=True)


def test_graph_checks_catch_closure_captured_weights(zoo_ctx):
    """Weights captured by closure instead of passed as args — the
    large-constant rule fails fit() in 'raise' mode and only warns in
    'warn' mode."""
    big = np.ones((1024, 512), np.float32)          # 2 MiB

    def leaky_loss(y, y_hat):
        # drags a 2 MiB host array into the jaxpr as a constant
        return ((y_hat - y) ** 2).mean() + 0.0 * jnp.asarray(big).sum()

    with pytest.raises(GraphLintError, match="large-constant"):
        _toy_fit("raise", loss=leaky_loss)
    est = _toy_fit("warn", loss=leaky_loss)          # logs, trains anyway
    assert est.trainer_state.iteration == 2


# ------------------------------------------- model-load-time fused-path check

def _quantized_im(np_rng, np):
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L

    m = Sequential([L.Dense(64, activation="relu", input_shape=(32,)),
                    L.Dense(8)])
    m.compile(optimizer="sgd", loss="mse")
    x = np_rng.normal(size=(32, 32)).astype(np.float32)
    m.fit(x, np.zeros((32, 8), np.float32), batch_size=16, nb_epoch=1)
    return InferenceModel(max_batch_size=8).load(m).quantize_int8(
        min_elements=64)


def test_inference_model_fused_check(zoo_ctx, monkeypatch, np_rng):
    monkeypatch.setenv("ZOO_INT8_FUSED", "interpret")
    im = _quantized_im(np_rng, np)
    x = np_rng.normal(size=(4, 32)).astype(np.float32)
    # healthy fused path: clean in raise mode
    assert im.check_fused_dispatch(x, mode="raise") == []
    # break the fused tier (the router finds no shape tileable and sends
    # everything to lax — the regression class): caught at model-load time
    from analytics_zoo_tpu.ops import int8_fused

    monkeypatch.setattr(int8_fused, "resolve_blocks", lambda *a, **k: None)
    findings = im.check_fused_dispatch(x, mode="warn")
    assert {f.rule for f in findings} == {"fused-int8-dispatch"}
    with pytest.raises(GraphLintError, match="fused-int8-dispatch"):
        im.check_fused_dispatch(x, mode="raise")


def test_serving_warmup_runs_fused_check(zoo_ctx, monkeypatch, np_rng):
    """The serving engine's _warm_model catches a broken fused path at
    model-LOAD time when config.graph_checks='raise'."""
    monkeypatch.setenv("ZOO_INT8_FUSED", "interpret")
    from analytics_zoo_tpu.serving.config import ServingConfig
    from analytics_zoo_tpu.serving.engine import ClusterServing

    im = _quantized_im(np_rng, np)
    cfg = ServingConfig(int8=True, warmup_shape=(32,), graph_checks="raise")
    cs = ClusterServing(model=im, config=cfg)
    cs._warm_model()                                  # healthy: no raise
    from analytics_zoo_tpu.ops import int8_fused

    monkeypatch.setattr(int8_fused, "resolve_blocks", lambda *a, **k: None)
    im._compiled.clear()
    with pytest.raises(GraphLintError, match="fused-int8-dispatch"):
        cs._warm_model()
