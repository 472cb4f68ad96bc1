"""Memory lint tier tests (ISSUE 12).

Golden fixtures per rule in both polarities (``donation-missed`` AST +
trace-time, ``cache-alias``, ``hbm-budget``, ``peak-temporary``), the
live-range analyzer's donation credit and scan awareness, the runtime
allocation witness (sample/aggregate/dump/load round-trip, budget and
divergence cross-checks, CLI mode), the ``TrainConfig.hbm_budget_mb`` /
``donate_state`` enforcement at ``fit()`` start under
``graph_checks="raise"``, the decode-warmup ``cache-alias`` hook, and the
bench-facing decode-memory invariant (donating the KV pool removes the
second pool-sized buffer from both the static estimate and the compiled
buffer table).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.analysis import (GraphLintError, RuleContext,
                                        check_memory_witness, lint_source,
                                        profile_jaxpr)
from analytics_zoo_tpu.analysis.rules.memory import (flatten_donation,
                                                     lint_donation,
                                                     lint_memory)
from analytics_zoo_tpu.common import memwitness as mw

pytestmark = pytest.mark.analysis


def _one(findings, rule):
    assert len(findings) == 1, [str(f) for f in findings]
    assert findings[0].rule == rule, str(findings[0])
    return findings[0]


# ----------------------------------------------------- live-range analyzer

def _cache_step(params, cache, x):
    c = cache["k"]
    for i in range(2):
        c = c.at[i].set(c[i] + x @ params)
    return x @ params, {"k": c}


def _cache_jaxpr():
    return jax.make_jaxpr(_cache_step)(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        {"k": jax.ShapeDtypeStruct((2, 64, 64), jnp.float32)},
        jax.ShapeDtypeStruct((64, 64), jnp.float32))


POOL = 2 * 64 * 64 * 4


def test_profile_donation_credit_removes_second_pool():
    closed = _cache_jaxpr()
    plain = profile_jaxpr(closed)
    donated = profile_jaxpr(closed, donated_invars=[False, True, False])
    # the threaded cache costs a second pool when un-donated; the donation
    # credit (in-place scatter chain) removes exactly that buffer
    assert plain.peak_live_bytes - donated.peak_live_bytes >= POOL
    assert donated.aliased_out_bytes >= POOL
    assert plain.temporaries[0].nbytes == POOL   # the scatter copy is top-1
    assert plain.peak_eqn is not None


N_LAYER_LEAVES = 6


def _per_layer_cache_step(params, cache, x):
    """``_cache_step``'s twin on the serving layout: one leaf per layer,
    each scattered into and returned, nothing sliced out of a stack."""
    y = x @ params
    return y, {"k": tuple(c.at[0].set(c[0] + y[0]) for c in cache["k"])}


def test_profile_donation_credit_covers_every_per_layer_leaf():
    leaf = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    closed = jax.make_jaxpr(_per_layer_cache_step)(
        leaf, {"k": (leaf,) * N_LAYER_LEAVES}, leaf)
    pool = N_LAYER_LEAVES * 64 * 64 * 4
    plain = profile_jaxpr(closed)
    donated = profile_jaxpr(
        closed, donated_invars=[False] + [True] * N_LAYER_LEAVES + [False])
    # un-donated, every leaf's scatter makes a new buffer beside its input:
    # a second pool in all (the peaks also differ by a few row-sized
    # temporaries). Donated, each leaf aliases its own output.
    assert plain.peak_live_bytes - donated.peak_live_bytes >= pool - 1024
    assert donated.aliased_out_bytes >= pool
    # donating half the leaves buys half the credit
    half = profile_jaxpr(
        closed, donated_invars=[False] + [True, False] * (N_LAYER_LEAVES // 2)
        + [False])
    assert half.aliased_out_bytes == pool // 2


def test_profile_scan_body_counts_once():
    """A scan body's temporary contributes its size ONCE (buffers are
    reused per iteration), and is tagged in_loop."""

    def scanned(xs):
        def body(c, x):
            t = jnp.outer(x, x)          # (64, 64) temp per iteration
            return c + t.sum(), t.sum()
        return jax.lax.scan(body, 0.0, xs)

    closed = jax.make_jaxpr(scanned)(
        jax.ShapeDtypeStruct((100, 64), jnp.float32))
    prof = profile_jaxpr(closed)
    temp = 64 * 64 * 4
    # peak ~= xs + one body temp (+ small carries) — NOT 100 body temps
    assert prof.peak_live_bytes < 100 * 64 * 4 + 3 * temp
    assert any(t.in_loop and t.nbytes == temp for t in prof.temporaries)


# --------------------------------------------------- jaxpr-layer rule goldens

def test_golden_hbm_budget_both_polarities():
    closed = _cache_jaxpr()
    over = RuleContext(where="fixture", hbm_budget_bytes=2 * POOL)
    f = _one(lint_memory(closed, ctx=over, rules=["hbm-budget"]),
             "hbm-budget")
    assert dict(f.data)["budget_bytes"] == 2 * POOL
    under = RuleContext(where="fixture", hbm_budget_bytes=64 * POOL)
    assert lint_memory(closed, ctx=under, rules=["hbm-budget"]) == []


def test_golden_peak_temporary_both_polarities():
    def blowup(x):
        return jnp.outer(x, x).sum()         # (4096, 4096) temp vs 16KiB arg

    closed = jax.make_jaxpr(blowup)(
        jax.ShapeDtypeStruct((4096,), jnp.float32))
    ctx = RuleContext(where="fixture")
    fs = lint_memory(closed, ctx=ctx, rules=["peak-temporary"])
    assert fs and all(f.rule == "peak-temporary" for f in fs)
    assert fs[0].severity == "warning"
    assert dict(fs[0].data)["nbytes"] == 4096 * 4096 * 4

    def tame(x):
        return (x * 2).sum()

    closed = jax.make_jaxpr(tame)(jax.ShapeDtypeStruct((4096,), jnp.float32))
    assert lint_memory(closed, ctx=ctx, rules=["peak-temporary"]) == []


def test_golden_cache_alias_both_polarities():
    closed = _cache_jaxpr()
    cache_avals = [((2, 64, 64), "float32")]
    bad = RuleContext(where="fixture", decode_cache_avals=cache_avals,
                      donated_invars=[False, False, False])
    f = _one(lint_memory(closed, ctx=bad, rules=["cache-alias"]),
             "cache-alias")
    assert "not donated" in f.message
    good = RuleContext(where="fixture", decode_cache_avals=cache_avals,
                       donated_invars=[False, True, False])
    assert lint_memory(closed, ctx=good, rules=["cache-alias"]) == []


def test_golden_trace_time_donation_missed_both_polarities():
    closed = _cache_jaxpr()
    # cache is dead after the call (caller rebinds), matches an output
    bad = RuleContext(where="fixture",
                      dead_invars=[False, True, False],
                      donated_invars=[False, False, False])
    f = _one(lint_donation(closed, bad), "donation-missed")
    assert dict(f.data)["missed_bytes"] == POOL
    good = RuleContext(where="fixture",
                       dead_invars=[False, True, False],
                       donated_invars=[False, True, False])
    assert lint_donation(closed, good) == []


def test_flatten_donation():
    assert flatten_donation([2, 3, 1], (0, 2)) == [True, True, False, False,
                                                   False, True]


# ----------------------------------------------------------- AST-layer golden

_AST_BAD = """
import jax

class Loop:
    def __init__(self, fn):
        self._step = jax.jit(fn)

    def run(self, state, batch):
        state, aux = self._step(state, batch)
        return state, aux
"""

_AST_GOOD = _AST_BAD.replace("jax.jit(fn)",
                             "jax.jit(fn, donate_argnums=(0,))")

_AST_UNKNOWN = _AST_BAD.replace("jax.jit(fn)",
                                "jax.jit(fn, donate_argnums=donate)")

_AST_FACTORY = """
import jax

class Loop:
    def _make(self):
        return jax.jit(self._fn)

    def fit(self):
        self._step = self._make()
        self.state, aux = self._step(self.state, 1)
"""

_AST_CACHE_HOP = """
import jax

class Eval:
    def build(self, key, fn):
        self._cache[key] = jax.jit(fn)

    def run(self, key, accs, batch):
        step = self._cache[key]
        accs = step(accs, batch)
        return accs
"""

_AST_DEVICE_PUT = """
import jax

def stage(params):
    params = jax.device_put(params)
    return params
"""


def test_golden_donation_missed_ast_both_polarities():
    fs, _ = lint_source(_AST_BAD, "fix.py", rules=["donation-missed"])
    f = _one(fs, "donation-missed")
    assert "donate_argnums=(0,)" in f.message
    fs, _ = lint_source(_AST_GOOD, "fix.py", rules=["donation-missed"])
    assert fs == []
    # donation present but not statically resolvable → silent, not a guess
    fs, _ = lint_source(_AST_UNKNOWN, "fix.py", rules=["donation-missed"])
    assert fs == []


def test_donation_missed_ast_factory_and_cache_hop():
    fs, _ = lint_source(_AST_FACTORY, "fix.py", rules=["donation-missed"])
    f = _one(fs, "donation-missed")
    assert "self.state" in f.message
    fs, _ = lint_source(_AST_CACHE_HOP, "fix.py", rules=["donation-missed"])
    f = _one(fs, "donation-missed")
    assert "accs" in f.message


def test_donation_missed_ast_device_put_and_suppression():
    fs, _ = lint_source(_AST_DEVICE_PUT, "fix.py", rules=["donation-missed"])
    f = _one(fs, "donation-missed")
    assert "device_put" in f.message
    suppressed = _AST_DEVICE_PUT.replace(
        "    params = jax.device_put(params)",
        "    # zoo-lint: disable=donation-missed\n"
        "    params = jax.device_put(params)")
    fs, ns = lint_source(suppressed, "fix.py", rules=["donation-missed"])
    assert fs == [] and ns == 1
    donated = _AST_DEVICE_PUT.replace("jax.device_put(params)",
                                      "jax.device_put(params, donate=True)")
    fs, _ = lint_source(donated, "fix.py", rules=["donation-missed"])
    assert fs == []


# ------------------------------------------------------------ runtime witness

@pytest.fixture()
def witness_env(tmp_path, monkeypatch):
    path = str(tmp_path / "mem_witness.jsonl")
    monkeypatch.setenv("ZOO_TPU_MEM_WITNESS", path)
    mw.reset_witness()
    yield path
    monkeypatch.delenv("ZOO_TPU_MEM_WITNESS", raising=False)
    mw.reset_witness()


def test_witness_disabled_is_noop(monkeypatch):
    monkeypatch.delenv("ZOO_TPU_MEM_WITNESS", raising=False)
    mw.reset_witness()
    mw.sample("nowhere")
    mw.note_static("nowhere", 123)
    assert mw.witness_samples() == {}
    assert mw.witness_statics() == {}


def test_witness_sample_aggregate_dump_load_roundtrip(witness_env):
    x = jnp.ones((256, 4), jnp.float32)      # keep a known array live
    for _ in range(3):
        mw.sample("test.site")
    mw.note_static("test.site", 12345, budget_bytes=99999)
    agg = mw.witness_samples()["test.site"]
    assert agg["n"] == 3
    assert agg["max_live_bytes"] >= x.nbytes
    assert agg["min_live_bytes"] <= agg["max_live_bytes"]
    mw.dump_witness(witness_env)
    # a second process' dump appends and merges
    mw.dump_witness(witness_env)
    samples, statics = mw.load_witness(witness_env)
    assert samples["test.site"]["n"] == 6
    assert samples["test.site"]["max_live_bytes"] == agg["max_live_bytes"]
    assert statics["test.site"] == {"peak_bytes": 12345,
                                    "budget_bytes": 99999}


def test_check_memory_witness_budget_and_divergence():
    gib = 1 << 30
    samples = {"s": {"n": 5, "min_live_bytes": 10, "max_live_bytes": gib,
                     "last_live_bytes": gib, "max_bytes_in_use": None}}
    # budget exceeded (site-recorded budget wins over the global fallback)
    fs = check_memory_witness(samples, {"s": {"budget_bytes": gib // 2}})
    f = _one(fs, "hbm-budget")
    assert f.severity == "error"
    # global fallback budget
    fs = check_memory_witness(samples, {}, budget_bytes=gib // 2)
    _one(fs, "hbm-budget")
    # divergence: measured far past the static estimate → warning
    fs = check_memory_witness(samples, {"s": {"peak_bytes": gib // 8}})
    f = _one(fs, "mem-witness-divergence")
    assert f.severity == "warning"
    # a big factor but a tiny absolute gap stays silent (test-sized
    # processes over toy estimates are trivia, not findings)
    small = {"s": {"n": 1, "min_live_bytes": 10, "max_live_bytes": 1000,
                   "last_live_bytes": 1000, "max_bytes_in_use": None}}
    assert check_memory_witness(small, {"s": {"peak_bytes": 100}}) == []
    # in-budget, in-line with the estimate → silent
    assert check_memory_witness(
        samples, {"s": {"peak_bytes": gib, "budget_bytes": 2 * gib}}) == []


def test_cli_mem_witness_mode(witness_env, capsys):
    from analytics_zoo_tpu.analysis.__main__ import main

    anchor = jnp.ones((64,), jnp.float32)    # guarantees live bytes > 0
    mw.sample("cli.site")
    del anchor
    mw.note_static("cli.site", 1)
    mw.dump_witness(witness_env)
    # in budget (none declared), divergence gap under the absolute floor
    assert main(["--mem-witness", witness_env, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["errors"] == 0 and "cli.site" in out["mem_sites"]
    # a microscopic global budget makes it an error exit
    assert main(["--mem-witness", witness_env,
                 "--budget-mb", "0.000001"]) == 1


# ------------------------------------------- fit-start enforcement (raise)

def _toy_fit(graph_checks, **cfg_kw):
    from analytics_zoo_tpu.common import TrainConfig
    from analytics_zoo_tpu.engine import Estimator
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = rng.normal(size=(64, 4)).astype(np.float32)
    model = Sequential([L.Dense(8, activation="relu", input_shape=(16,)),
                        L.Dense(4)])
    est = Estimator(model, optimizer="sgd", loss="mse",
                    config=TrainConfig(shuffle=False,
                                       log_every_n_steps=10 ** 9,
                                       graph_checks=graph_checks, **cfg_kw))
    est.fit((x, y), batch_size=32, epochs=1)
    return est


def test_fit_start_catches_undonated_train_step(zoo_ctx):
    """The acceptance drill: donate_state=False under graph_checks='raise'
    fails fit() BEFORE the first compile; the default (donated) passes."""
    with pytest.raises(GraphLintError, match="donation-missed"):
        _toy_fit("raise", donate_state=False)
    est = _toy_fit("raise")                  # donate_state=True default
    assert est.trainer_state.iteration == 2


def test_fit_start_hbm_budget_raise_and_pass(zoo_ctx):
    with pytest.raises(GraphLintError, match="hbm-budget"):
        _toy_fit("raise", hbm_budget_mb=0.001)
    est = _toy_fit("raise", hbm_budget_mb=512.0)
    assert est.trainer_state.iteration == 2


def test_fit_notes_static_peak_into_witness(zoo_ctx, witness_env):
    _toy_fit("warn", hbm_budget_mb=512.0)
    statics = mw.witness_statics()
    assert statics["estimator.step"]["peak_bytes"] > 0
    assert statics["estimator.step"]["budget_bytes"] == 512 * 2 ** 20
    # the epoch boundary sampled at least once
    assert mw.witness_samples()["estimator.step"]["n"] >= 1


# ------------------------------------------------- decode warmup (cache-alias)

def _tiny_batcher(**kw):
    from analytics_zoo_tpu.models.transformer import TransformerLM
    from analytics_zoo_tpu.serving.generation import ContinuousBatcher

    model = TransformerLM(vocab=64, hidden_size=32, n_block=2, n_head=2,
                          seq_len=64)
    params, _ = model.build(jax.random.PRNGKey(0))
    return ContinuousBatcher(model, params, n_slots=2, page_size=16,
                             max_seq_len=64, autostart=False, **kw)


def test_decode_cache_alias_both_polarities():
    b = _tiny_batcher(donate_cache=False)
    try:
        with pytest.raises(GraphLintError, match="cache-alias"):
            b.check_decode_stability("raise")
        fs = b.check_decode_stability("warn")
        # every layer's k and v pool shares (shape, dtype) — ONE deduped
        # finding for the one missing donate_argnums, counting all the leaves
        # (2 blocks x {k, v})
        f = _one(fs, "cache-alias")
        assert dict(f.data)["leaves"] == 4
    finally:
        b.close()
    b = _tiny_batcher()                      # donate_cache=True default
    try:
        assert b.check_decode_stability("raise") == []
    finally:
        b.close()


def test_decode_memory_donation_removes_second_pool():
    """The bench gate's invariant, unit-level: static peak drops by ≥ one
    pool under donation and the compiled executable aliases the pool."""
    b = _tiny_batcher()
    try:
        mem = b.decode_memory()
        assert mem["donate_cache"]
        saved = (mem["static_peak_bytes_undonated"]
                 - mem["static_peak_bytes"])
        assert saved >= 0.4 * mem["cache_bytes"], mem
        alias = mem["compiled"].get("alias_size_in_bytes")
        if alias is not None:                # backend-dependent
            assert alias >= mem["cache_bytes"], mem
    finally:
        b.close()


def test_decode_hbm_budget_enforced():
    b = _tiny_batcher(hbm_budget_bytes=1024)
    try:
        with pytest.raises(GraphLintError, match="hbm-budget"):
            b.check_decode_stability("raise")
    finally:
        b.close()


def test_decode_flat_witness(witness_env):
    """The generation quick gate's witness story: device bytes sampled at
    every decode step stay flat across a whole generation."""
    b = _tiny_batcher()
    b.start()
    try:
        out = b.generate([1, 2, 3], max_new_tokens=12, timeout_s=60)
        assert len(out) == 12
    finally:
        b.close()
    agg = mw.witness_samples()["serving.decode"]
    assert agg["n"] >= 10
    assert agg["max_live_bytes"] <= 1.25 * agg["min_live_bytes"]


# ----------------------------------------------- serving warmup (hbm-budget)

def test_inference_check_memory_budget(np_rng):
    from analytics_zoo_tpu.inference import InferenceModel
    from analytics_zoo_tpu.nn import Sequential
    from analytics_zoo_tpu.nn import layers as L

    model = Sequential([L.Dense(8, input_shape=(16,))])
    params, state = model.build(jax.random.PRNGKey(0))
    im = InferenceModel(max_batch_size=8).load(model, params=params,
                                               state=state)
    x = np_rng.normal(size=(4, 16)).astype(np.float32)
    with pytest.raises(GraphLintError, match="hbm-budget"):
        im.check_memory(x, mode="raise", budget_bytes=8)
    assert im.check_memory(x, mode="raise",
                           budget_bytes=64 * 2 ** 20) == []
    assert im.check_memory(x, mode="off") == []


def test_serving_config_hbm_budget_yaml(tmp_path):
    from analytics_zoo_tpu.serving import ServingConfig

    p = tmp_path / "c.yaml"
    p.write_text("memory:\n  hbm_budget_mb: 64\n")
    assert ServingConfig.from_yaml(str(p)).hbm_budget_mb == 64.0
    p.write_text("hbm_budget_mb: 32\n")
    assert ServingConfig.from_yaml(str(p)).hbm_budget_mb == 32.0
    p.write_text("model:\n  path: /x\n")
    assert ServingConfig.from_yaml(str(p)).hbm_budget_mb is None
