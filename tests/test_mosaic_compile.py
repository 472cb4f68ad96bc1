"""Mosaic compiles the Pallas kernels for a v5e, checked without a chip.

Every other kernel test runs the Pallas interpreter, and a kernel that only
ever ran interpreted can be one Mosaic refuses: it refused the int8 conv (a
tap offset it could not prove sublane-aligned), the paged kernel at prefill
widths (16 MiB of scoped VMEM) and the flash kernel under 128 tokens (an
unaligned lane store) before anything here ran on a TPU. libtpu compiles for
a described topology from a CPU host, so these compile ahead of time, with
``interpret=False`` said out loud, the shapes ``chip_smoke.py`` runs in its
train, generation and int8 phases, plus the widest the serving config admits.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from analytics_zoo_tpu.ops import int8_fused
from analytics_zoo_tpu.ops.flash_attention import flash_attention
from analytics_zoo_tpu.ops.paged_attention import paged_attention

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def v5e_2x2():
    """The described v5e 2x2 topology (four chips, none attached)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu, or one that knows no v5e
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(scope="module")
def v5e(v5e_2x2):
    """``compile(fn, (shape, dtype), ..., **jit_kw)`` for one device of a
    v5e 2x2: the compiled executable, which holds a Mosaic call."""
    sharding = SingleDeviceSharding(v5e_2x2.devices[0])

    def compile(fn, *avals, **jit_kw):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                for shape, dtype in avals]
        # the precision a TPU process runs at, not conftest's "highest":
        # Mosaic takes an fp32 contract precision on f32 operands only
        # ("Bad lhs type" on bf16 and int8)
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    return compile


def test_flash_forward_and_backward_at_the_training_shape(v5e):
    qkv = ((8, 2048, 8, 128), BF16)

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(
            *a, True, None, None, False).astype(F32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    assert v5e(grads, qkv, qkv, qkv).as_text().count("tpu_custom_call") == 3


# the serving prefill of gen-docs-batch (one 2,048-token prompt, 16 heads of
# 128; forward alone) and an explicit flash call at GPT-2 medium's widths
# (head 64: the statistics' lanes are cut to the accumulator's 64)
@pytest.mark.parametrize("shape,calls", [((1, 2048, 16, 128), 1),
                                         ((4, 1024, 16, 64), 3)])
def test_flash_at_the_serving_shape_and_at_head_64(v5e, shape, calls):
    qkv = (shape, BF16)

    def forward(q, k, v):
        return flash_attention(q, k, v, True, None, None, False)

    def grads(q, k, v):
        return jax.grad(lambda *a: forward(*a).astype(F32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    text = v5e(forward if calls == 1 else grads, qkv, qkv, qkv).as_text()
    assert text.count("tpu_custom_call") == calls
    assert "zoo_flash_fwd" in text


# decode, speculative verify, and the widest query the config admits: a
# prefix-hit suffix bucket as long as gen_max_seq_len (one slot, table one
# chunk wider than the slot's pages); then the benchmark's serving cell as it
# is (Cerebras-GPT-1.3B: 32 slots, 16 heads of 128, 2,304 pages), its decode
# step and a 2,048-token chunk
@pytest.mark.parametrize("slots,q_len,table,heads,pages,dtype", [
    (8, 1, 128, 8, 1025, BF16), (8, 1, 128, 8, 1025, F32),
    (8, 4, 128, 8, 1025, BF16), (8, 4, 128, 8, 1025, F32),
    (1, 2048, 256, 8, 1025, BF16), (1, 2048, 256, 8, 1025, F32),
    (32, 1, 128, 16, 2304, BF16), (1, 2048, 128, 16, 2304, BF16)])
def test_paged_attention_at_the_serving_shapes(v5e, slots, q_len, table,
                                               heads, pages, dtype):
    pool = ((pages, 16, heads, 128), dtype)
    text = v5e(lambda q, k, v, tb, ln: paged_attention(
        q, k, v, tb, ln, page_size=16, interpret=False),
        ((slots, q_len, heads, 128), dtype), pool, pool,
        ((slots, table), I32), ((slots,), I32)).as_text()
    # one Mosaic call, under the name the benchmark's readers select by
    assert text.count("tpu_custom_call") == 1
    assert "zoo_paged_attention" in text


def test_decode_step_of_the_serving_cell_reads_the_served_tree(v5e,
                                                               monkeypatch):
    """The whole decode step of the benchmark's serving cell, given the tree
    a ``ContinuousBatcher`` serves under its bf16 policy: 3.05 GB of
    parameters among the arguments where the f32 tree is 5.67, no temporary
    copy of a weight, the pool aliased, one paged kernel a block."""
    from analytics_zoo_tpu.models.transformer import TransformerLM
    from analytics_zoo_tpu.nn.module import precision_policy

    slots, page, pages, pps, blocks = 32, 16, 2304, 128, 24
    m = TransformerLM(vocab=50257, hidden_size=2048, n_block=blocks,
                      n_head=16, seq_len=2048, intermediate_size=8192)
    given = jax.eval_shape(lambda: m.build(jax.random.PRNGKey(0))[0])
    leaves, treedef = jax.tree_util.tree_flatten(given)
    served = [(leaf.shape, BF16 if cast else leaf.dtype) for leaf, cast in
              zip(leaves, jax.tree_util.tree_leaves(m.cast_at_use(given)))]
    n = len(served)
    pools = [((pages, page, 16, 128), BF16)] * (2 * blocks)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def step(*flat):
        kv = flat[n:n + 2 * blocks]
        with precision_policy(compute_dtype="bfloat16"):
            return m.decode_step(
                treedef.unflatten(flat[:n]),
                {"k": kv[:blocks], "v": kv[blocks:]},
                *flat[n + 2 * blocks:], page_size=page)

    compiled = v5e(
        step, *served, *pools, ((slots,), I32), ((slots,), I32),
        ((slots, pps), I32), ((slots,), jnp.uint32), ((slots,), jnp.uint32),
        ((slots,), F32), donate_argnums=tuple(range(n, n + 2 * blocks)))
    mem = compiled.memory_analysis()
    pool_bytes = 2 * blocks * pages * page * 16 * 128 * 2
    param_bytes = mem.argument_size_in_bytes - pool_bytes
    assert 3.05e9 < param_bytes < 3.06e9
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 64e6
    assert compiled.as_text().count("zoo_paged_attention") >= blocks


# Olmo-Hybrid's serving cell (gen-olmoh-reason-steady): 48 slots, 30 linear
# heads of key 96 and value 192 (a slot's state is one 96 x 5,760 float32
# block, aliased in to out), prompts of 32 to 1,024 tokens in chunks of 64;
# and the paged kernel at the 32 head slots its full layers' pools hold (the
# 30 heads themselves Mosaic refuses: a slice of 30 on an axis tiled by 8)
def test_gated_delta_decode_at_the_serving_shape(v5e):
    from analytics_zoo_tpu.ops.gated_delta import gdn_decode

    slots, h, dk, dv = 48, 30, 96, 192
    compiled = v5e(
        lambda *a: gdn_decode(*a, interpret=False),
        ((slots, dk, h * dv), F32), ((slots, h, dk), F32),
        ((slots, h, dk), F32), ((slots, h, dv), F32), ((slots, h), F32),
        ((slots, h), F32), ((slots,), jnp.bool_), donate_argnums=(0,))
    assert "zoo_gdn_decode" in compiled.as_text()
    # the state is updated where it lies: no second copy of it
    assert compiled.memory_analysis().alias_size_in_bytes \
        == slots * dk * h * dv * 4


@pytest.mark.parametrize("tokens", [32, 1024])
def test_gated_delta_chunk_scan_at_the_serving_buckets(v5e, tokens):
    from analytics_zoo_tpu.ops.gated_delta import gated_delta_chunked

    h, dk, dv = 30, 96, 192
    qk, v, gate = (((1, tokens, h, dk), F32), ((1, tokens, h, dv), F32),
                   ((1, tokens, h), F32))
    text = v5e(lambda *a: gated_delta_chunked(*a, kernel=True,
                                              interpret=False),
               qk, qk, v, gate, gate).as_text()
    assert "zoo_gdn_chunk_fwd" in text


def test_paged_attention_at_the_hybrid_cells_pool(v5e):
    pool = ((3584, 16, 32, 128), BF16)
    text = v5e(lambda q, k, v, tb, ln: paged_attention(
        q, k, v, tb, ln, page_size=16, interpret=False),
        ((48, 1, 32, 128), BF16), pool, pool, ((48, 256), I32),
        ((48,), I32)).as_text()
    assert "zoo_paged_attention" in text


# Falcon-H1's serving cell (gen-falconh1-chat-steady): 64 slots, 32
# state-space heads of 128 with a state of 256 (a slot's state a layer is
# 32 x 256 x 128 float32, aliased in to out, 8 heads a program), prompts of
# 32 to 1,024 tokens in chunks of 128; and the paged kernel at the 4 KV heads
# its pools hold, the 5 query heads of each as rows of that head's dots
def test_ssd_decode_at_the_serving_shape(v5e):
    from analytics_zoo_tpu.ops.ssd import ssd_decode

    slots, h, p, n, g = 64, 32, 128, 256, 2
    compiled = v5e(
        lambda *a: ssd_decode(*a, interpret=False),
        ((slots, h, n, p), F32), ((slots, h, p), F32), ((slots, h), F32),
        ((h,), F32), ((slots, g, n), F32), ((slots, g, n), F32),
        ((slots,), jnp.bool_), donate_argnums=(0,))
    assert "zoo_ssd_decode" in compiled.as_text()
    # the state is updated where it lies: no second copy of it
    assert compiled.memory_analysis().alias_size_in_bytes \
        == slots * h * n * p * 4


@pytest.mark.parametrize("tokens", [32, 1024])
def test_ssd_chunk_scan_at_the_serving_buckets(v5e, tokens):
    from analytics_zoo_tpu.ops.ssd import ssd_chunked

    h, p, n, g = 32, 128, 256, 2
    text = v5e(lambda *a: ssd_chunked(*a, chunk=128, kernel=True,
                                      interpret=False),
               ((1, tokens, h, p), F32), ((1, tokens, h), F32), ((h,), F32),
               ((1, tokens, g, n), F32), ((1, tokens, g, n), F32)).as_text()
    assert "zoo_ssd_chunk_fwd" in text


@pytest.mark.parametrize("q_len", [1, 4])
def test_paged_attention_at_grouped_kv_heads(v5e, q_len):
    pool = ((8193, 16, 4, 128), BF16)
    text = v5e(lambda q, k, v, tb, ln: paged_attention(
        q, k, v, tb, ln, page_size=16, interpret=False),
        ((64, q_len, 20, 128), BF16), pool, pool, ((64, 128), I32),
        ((64,), I32)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert "zoo_paged_attention" in text


def test_decode_step_of_the_falcon_h1_cell_fits_the_chip(v5e, monkeypatch):
    """The whole decode step of ``gen-falconh1-chat-steady`` at the widths
    of its configuration's file: 10.51 GB of bfloat16 parameters and 3.23 GB
    of cache among the arguments (pages 12,288 B a token: 1.61 GB; state
    1.61 GB; tails 12 MB), every leaf of the cache aliased, temporaries of
    megabytes, both mixers' kernels in every layer."""
    import json
    import os

    from analytics_zoo_tpu.models.falcon_h1 import FalconH1LM
    from analytics_zoo_tpu.nn.module import precision_policy

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "falcon-h1-34b.json")) as f:
        config = json.load(f)
    kwargs = {k: config[v[1:]] if isinstance(v, str) and v.startswith("@")
              else v for k, v in config["build"]["kwargs"].items()}
    sizes = config["serving"]["ServingConfig"]
    slots, page, pages = (sizes["gen_slots"], sizes["gen_page_size"],
                          sizes["gen_pages"])
    pps, layers = sizes["gen_max_seq_len"] // page, kwargs["n_layer"]
    m = FalconH1LM(**kwargs)
    with precision_policy(param_dtype="bfloat16"):
        given = jax.eval_shape(lambda: m.build(jax.random.PRNGKey(0))[0])
    leaves, treedef = jax.tree_util.tree_flatten(given)
    served = [(leaf.shape, leaf.dtype) for leaf in leaves]
    n = len(served)
    cache = ([((pages, page, 4, 128), BF16)] * (2 * layers)
             + [((slots, 32, 256, 128), F32)] * layers
             + [((slots, 3, 5120), BF16)] * layers)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def step(*flat):
        kv = flat[n:n + 4 * layers]
        with precision_policy(compute_dtype="bfloat16"):
            return m.decode_step(
                treedef.unflatten(flat[:n]),
                {"k": kv[:layers], "v": kv[layers:2 * layers],
                 "ssm": kv[2 * layers:3 * layers], "conv": kv[3 * layers:]},
                *flat[n + 4 * layers:], page_size=page)

    compiled = v5e(
        step, *served, *cache, ((slots,), I32), ((slots,), I32),
        ((slots, pps), I32), ((slots,), jnp.uint32), ((slots,), jnp.uint32),
        ((slots,), F32), donate_argnums=tuple(range(n, n + 4 * layers)))
    mem = compiled.memory_analysis()
    page_bytes = 2 * layers * pages * page * 4 * 128 * 2
    assert page_bytes == pages * page * 12288
    cache_bytes = page_bytes + layers * slots * (32 * 256 * 128 * 4
                                                 + 3 * 5120 * 2)
    assert 10.50e9 < mem.argument_size_in_bytes - cache_bytes < 10.52e9
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 64e6
    text = compiled.as_text()
    assert text.count("zoo_paged_attention") >= layers
    assert text.count("zoo_ssd_decode") >= layers


@pytest.mark.parametrize("m", [1, 16, 512])
def test_fused_int8_matmul_at_the_mlp_shapes(v5e, m):
    v5e(lambda x, q, s: int8_fused.int8_matmul_fused(
        x, {"q": q, "scale": s}, interpret=False),
        ((m, 256), BF16), ((256, 512), I8), ((512,), F32))


@pytest.mark.parametrize("hw,cin,cout,k,dtype", [
    (56, 64, 64, 3, BF16), (14, 256, 256, 3, F32), (224, 3, 64, 7, BF16)])
def test_fused_int8_conv_at_resnet_shapes(v5e, hw, cin, cout, k, dtype):
    v5e(lambda x, q, s: int8_fused.int8_conv2d_fused(
        x, {"q": q, "scale": s}, padding="SAME", interpret=False),
        ((2, hw, hw, cin), dtype), ((k, k, cin, cout), I8), ((cout,), F32))


def _cell_params():
    """The four-chip training cell's own parameter tree, in bf16."""
    from analytics_zoo_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab=50257, hidden_size=2048, n_block=8, n_head=16,
                          seq_len=2048, intermediate_size=8192)
    tree = jax.eval_shape(lambda key: model.build(key)[0],
                          jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, BF16), tree)


_SYNTHETIC = {"blocks": jax.ShapeDtypeStruct((8, 24576, 2048), BF16),
              "head": jax.ShapeDtypeStruct((50257, 2048), BF16),
              "tokens": jax.ShapeDtypeStruct((50257, 2048), BF16),
              "positions": jax.ShapeDtypeStruct((2048, 2048), BF16),
              "norm": jax.ShapeDtypeStruct((2048,), BF16)}


#: a smaller tree whose head, 2,048 x 1,000, is no whole number of lane tiles
#: wide either: it too enters by its transpose's rows
_NARROW_HEAD = {"blocks": jax.ShapeDtypeStruct((4, 8192, 2048), BF16),
                "head": jax.ShapeDtypeStruct((2048, 1000), BF16),
                "norm": jax.ShapeDtypeStruct((2048,), BF16)}


@pytest.mark.parametrize(
    "tree,buckets,shard,head,own_rows,reshape_gb,concatenate_gb", [
        (lambda: _SYNTHETIC, 13, (23040, 512), (50257, 2048), 1.0, 0.1, 0.4),
        (_cell_params, 13, (23040, 512), (2048, 50257), 0.9996, 0.1, 0.4),
        (lambda: _NARROW_HEAD, 2, (16896, 512), (2048, 1000), 1.0, 0.1, 0.4)],
    ids=["synthetic", "cell", "narrow_head"])
def test_flat_exchange_reaches_the_chips_as_reduce_scatters(
        v5e_2x2, tree, buckets, shard, head, own_rows, reshape_gb,
        concatenate_gb):
    """The ZeRO-1 flat exchange at the four-chip training cell's size (613 M
    bf16 parameters, dp=4, Adam with f32 masters), compiled for the 2x2, on
    a tree of a few large leaves and on the cell's own 101: every bucket's
    reduction is a real ``reduce-scatter`` and every gather an
    ``all-gather``. This compiler rewrites a reduce-scatter whose shard is
    one contiguous block (a 1-D operand, or a scatter over the major
    dimension), or whose rows it cannot chunk, to an all-reduce of the whole
    operand: twice the wire bytes, and what the cell paid until its exchange
    was bucketed. And the matrices enter their buckets by their own rows,
    the head of 2,048 x 50,257 by its transpose's (a bitcast: this compiler
    keeps that parameter with its rows minor): the program re-tiles (a
    standalone ``reshape``) next to nothing and stacks (a standalone
    ``concatenate``) the two vocabulary-high matrices, where the cell's tree
    raveled into rows of 4,096 read 2.86 and 1.36 GB, and it walks no leaf
    row by row (no ``while``, no ``dynamic-update-slice``), as it did the
    head's ravel, nor transposes or copies it in an op of its own: so for
    the cell's head and for one of 2,048 x 1,000 in a smaller tree, the two
    shapes compiled here; where a compiler kept such a matrix with its
    columns minor, each transpose would be a pass over it. (23,040
    rows a bucket is a count this compiler reduces quickly; 23,168, 128 x
    181, took it half as long again on the chips: ``PERF.md`` section 6.)"""
    import re

    import numpy as np
    import optax
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from analytics_zoo_tpu.analysis.rules.collectives import entry_ops
    from analytics_zoo_tpu.parallel import update_sharding as upd

    mesh = Mesh(np.array(v5e_2x2.devices[:4]), ("dp",))
    tree = tree()
    meta = upd.flat_meta(tree, 4)
    assert meta.n_buckets == buckets and meta.shard_shape == shard
    assert round(meta.own_rows_share, 4) == own_rows
    tx = optax.adam(1e-4)
    opt = jax.eval_shape(lambda: upd.flat_opt_init(
        tx, jax.tree_util.tree_map(lambda l: jnp.zeros(l.shape, l.dtype),
                                   tree), meta, keep_master=True))
    opt_specs = jax.tree_util.tree_map(
        lambda l: P(None, "dp") if l.shape == meta.bucket_shape else P(), opt)

    def place(avals, specs):
        return jax.tree_util.tree_map(
            lambda l, s: jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
            avals, specs)

    replicated = jax.tree_util.tree_map(lambda _: P(), tree)
    step = jax.jit(shard_map(
        lambda p, g, o: upd.flat_exchange(p, g, o, meta, tx),
        mesh=mesh, in_specs=(replicated, replicated, opt_specs),
        out_specs=(replicated, opt_specs, P()), check_vma=False),
        donate_argnums=(0, 2))
    lowered = step.lower(place(tree, replicated), place(tree, replicated),
                         place(opt, opt_specs))
    text = lowered.as_text()
    assert text.count('stablehlo.reduce_scatter"') == 1
    assert text.count('stablehlo.all_gather"') == 1
    hlo = lowered.compile().as_text()
    entry = hlo[hlo.index("ENTRY"):]
    found = re.findall(r"= \S+ (all-reduce|reduce-scatter|all-gather)"
                       r"(?:-start)?\(", entry)
    # an all-gather the scheduler runs under other work is wrapped in an
    # async-collective fusion pair and no longer shows in ENTRY by name
    wrapped = len(re.findall(r"async-collective-start[.\d]* = ", entry))
    assert found.count("reduce-scatter") == meta.n_buckets, found
    assert found.count("all-gather") + wrapped == meta.n_buckets, found
    big = [shape for shape in re.findall(
        r"= (\S+) all-reduce(?:-start)?\(", entry) if "[]" not in shape]
    assert not big, big
    ops = entry_ops(hlo)
    assert ops.get("reshape", [0, 0])[1] < reshape_gb * 1e9, ops
    assert ops.get("concatenate", [0, 0])[1] < concatenate_gb * 1e9, ops
    assert not {"while", "dynamic-update-slice"} & set(ops), ops
    # by the name of an op or of the fusion that stands for it
    a, b = head
    moved = [m.group(0) for m in re.finditer(
        r"%(\S+) = \w+(\[[\d,]*\])\S* ([\w-]+)\(", entry)
        if m.group(2) in (f"[{a},{b}]", f"[{b},{a}]")
        and re.search("copy|transpose", m.group(1) + m.group(3))]
    assert not moved, moved
