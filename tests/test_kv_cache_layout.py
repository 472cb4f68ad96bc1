"""The KV cache's layout, as a property of the compiled programs: one pool
per layer, every pool aliased input to output when the cache is donated, and
no copy of a pool anywhere in the decode, verify, prefill, chunk and
copy-on-write executables. (A stacked ``(n_layers, ...)`` pool cost a copy of
one layer's pool out of it and back around every write: 69% of the device's
time in the chat cell, PERF.md section 6, PR 24.) Speed is the chip's to
show; what can be asserted on any backend is that the copies are not in the
program.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.models.falcon_h1 import FalconH1LM
from analytics_zoo_tpu.models.hybrid_lm import HybridLM
from analytics_zoo_tpu.models.transformer import TransformerLM
from analytics_zoo_tpu.ops.kv_cache import (KVCacheConfig, copy_page,
                                            init_cache)

pytestmark = pytest.mark.generation

LAYERS, SLOTS, PAGE, PAGES, SEQ = 3, 2, 4, 512, 32


MODELS = {
    "transformer": lambda: TransformerLM(
        vocab=64, hidden_size=32, n_block=LAYERS, n_head=2, seq_len=64),
    # pages and per-slot state through the same walker: two page layers
    # among three of slot state
    "hybrid": lambda: HybridLM(
        vocab=64, hidden_size=32, intermediate_size=48, n_head=2,
        layer_types=["linear_attention", "full_attention"] * 2
        + ["linear_attention"], linear_num_heads=2, linear_key_head_dim=8,
        linear_value_head_dim=16, seq_len=64),
    # both kinds in every layer: each layer's two mixers write their own
    # leaves in one visit
    "falcon_h1": lambda: FalconH1LM(
        vocab=64, hidden_size=32, intermediate_size=48, n_layer=LAYERS,
        n_head=4, n_kv_head=2, head_dim=8, mamba_n_heads=4, mamba_d_head=8,
        mamba_d_state=8, mamba_n_groups=2, mamba_chunk_size=8, seq_len=64),
}


@pytest.fixture(scope="module")
def rigs():
    # a pool (512 pages) far larger than anything the model computes, so a
    # pool-sized temporary cannot hide among the activations
    built = {}

    def rig(which):
        if which not in built:
            m = MODELS[which]()
            params, _ = m.build(jax.random.PRNGKey(0))
            cfg, cache = m.init_kv_cache(SLOTS, page_size=PAGE,
                                         max_seq_len=SEQ, n_pages=PAGES)
            built[which] = m, params, cfg, cache
        return built[which]

    return rig


def test_init_cache_is_one_pool_per_layer():
    cfg = KVCacheConfig(n_layers=3, n_heads=2, head_dim=8, n_slots=2,
                        page_size=4, pages_per_slot=4, dtype=jnp.bfloat16)
    cache = init_cache(cfg)
    assert sorted(cache) == ["k", "v"]
    for name in ("k", "v"):
        assert isinstance(cache[name], tuple) and len(cache[name]) == 3
        for pool in cache[name]:
            assert pool.shape == (cfg.total_pages, 4, 2, 8)
            assert pool.dtype == jnp.bfloat16
    # distinct buffers: a pool donated twice could not alias twice
    leaves = jax.tree_util.tree_leaves(cache)
    assert len({leaf.unsafe_buffer_pointer() for leaf in leaves}) == 6


def _programs(m, cfg):
    """name -> (function of (params, cache, *rest), avals of rest): the
    jitted lambdas of ``ContinuousBatcher.__init__``."""
    page = cfg.page_size
    sds = jax.ShapeDtypeStruct
    i32 = lambda *shape: sds(shape, jnp.int32)
    u32 = lambda *shape: sds(shape, jnp.uint32)
    sampling = (u32(SLOTS), u32(SLOTS), sds((SLOTS,), jnp.float32))
    table = i32(SLOTS, cfg.pages_per_slot)
    chunk = 8
    return {
        "decode_step": (
            lambda p, c, *a: m.decode_step(p, c, *a, page_size=page),
            (i32(SLOTS), i32(SLOTS), table) + sampling),
        "verify_step": (
            lambda p, c, *a: m.verify_step(p, c, *a, page_size=page),
            (i32(SLOTS, 3), i32(SLOTS), table) + sampling),
        "prefill": (
            lambda p, c, *a: m.prefill(p, c, *a, page_size=page),
            (i32(1, 16), i32(1), i32(1, cfg.pages_per_slot))),
        "prefill_chunk": (
            lambda p, c, *a: m.prefill_chunk(p, c, *a, page_size=page),
            (i32(1, chunk), i32(1), i32(1),
             i32(1, cfg.pages_per_slot + chunk // page))),
        "copy_page": (
            lambda p, c, *a: copy_page(c, *a), (i32(), i32())),
    }


_ENTRY_LINE = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<dtype>[a-z]\w*)"
    r"\[(?P<dims>[\d,]*)\]\S* (?P<op>[\w\-]+)\(")


def _entry_arrays(hlo_text):
    """(name, opcode, bytes) of every array-valued instruction of the
    optimised module's ENTRY computation."""
    lines = hlo_text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.startswith("ENTRY "))
    out = []
    for line in lines[start + 1:]:
        if line.startswith("}"):
            break
        m = _ENTRY_LINE.match(line)
        if not m:
            continue                    # tuple-typed: get-tuple-element etc
        n = int(np.prod([int(d) for d in m["dims"].split(",") if d] or [1]))
        bits = re.search(r"\d+$", m["dtype"])       # f32, bf16, s8; pred
        out.append((m["name"], m["op"],
                    n * (int(bits.group()) // 8 if bits else 1)))
    return out


def _pool_sized_copies(hlo_text, pool_bytes):
    """Array-valued ENTRY instructions at least one layer's pool large that
    are neither parameters nor the in-place writes."""
    in_place = ("scatter", "dynamic-update-slice")
    return [(name, op, nbytes)
            for name, op, nbytes in _entry_arrays(hlo_text)
            if nbytes >= pool_bytes and op != "parameter"
            and not any(w in name or w in op for w in in_place)]


def test_the_check_sees_the_copies_of_a_stacked_pool():
    """The negative control: the layout this replaced (slice a layer out of
    one stacked array, scatter, store it back), donated all the same."""
    def stacked_step(pool, rows, new):
        for i in range(LAYERS):
            pool = pool.at[i].set(pool[i].at[rows].set(new))
        return pool

    sds = jax.ShapeDtypeStruct
    compiled = jax.jit(stacked_step, donate_argnums=(0,)).lower(
        sds((LAYERS, PAGES, PAGE, 2, 16), jnp.float32),
        sds((SLOTS,), jnp.int32),
        sds((SLOTS, PAGE, 2, 16), jnp.float32)).compile()
    pool_bytes = PAGES * PAGE * 2 * 16 * 4
    assert _pool_sized_copies(compiled.as_text(), pool_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes >= pool_bytes


@pytest.mark.parametrize("which,program", [
    ("transformer", "decode_step"), ("transformer", "verify_step"),
    ("transformer", "prefill"), ("transformer", "prefill_chunk"),
    ("transformer", "copy_page"),
    # what a model with per-slot state is served by (the rest is refused)
    ("hybrid", "decode_step"), ("hybrid", "prefill"),
    ("falcon_h1", "decode_step"), ("falcon_h1", "prefill")])
def test_donated_cache_is_written_where_it_lies(rigs, which, program):
    m, params, cfg, cache = rigs(which)
    fn, rest = _programs(m, cfg)[program]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *rest).compile()
    leaves = jax.tree_util.tree_leaves(cache)
    pool_bytes = max(leaf.nbytes for leaf in leaves)
    text = compiled.as_text()

    # (a) every cache leaf is aliased input to output. jit drops copy_page's
    # unused params from the executable; the cache leaves are then its first
    # parameters.
    n_params = (0 if program == "copy_page"
                else len(jax.tree_util.tree_leaves(params)))
    header = text.split("\n", 1)[0]
    aliased = {int(i) for i in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    assert aliased == set(range(n_params, n_params + len(leaves))), header

    # (b) nothing pool-sized is made besides the in-place writes
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == sum(leaf.nbytes for leaf in leaves)
    assert mem.temp_size_in_bytes < pool_bytes, (
        f"{program}: {mem.temp_size_in_bytes} bytes of temporaries, one "
        f"layer's pool is {pool_bytes}")
    copies = _pool_sized_copies(text, pool_bytes)
    assert not copies, copies


def test_copy_page_copies_one_page_in_every_layer(np_rng):
    cfg = KVCacheConfig(n_layers=3, n_heads=2, head_dim=4, n_slots=2,
                        page_size=4, pages_per_slot=2)
    shape = (cfg.total_pages, 4, 2, 4)
    before = {name: tuple(np_rng.normal(size=shape).astype(np.float32)
                          for _ in range(3)) for name in ("k", "v")}
    src, dst = 3, 1
    after = jax.jit(copy_page)(
        jax.tree_util.tree_map(jnp.asarray, before), np.int32(src),
        np.int32(dst))
    assert jax.tree_util.tree_structure(after) == \
        jax.tree_util.tree_structure(before)
    others = [p for p in range(cfg.total_pages) if p != dst]
    for name in ("k", "v"):
        for layer in range(3):
            got = np.asarray(after[name][layer])
            want = before[name][layer]
            np.testing.assert_array_equal(got[dst], want[src])
            np.testing.assert_array_equal(got[others], want[others])
