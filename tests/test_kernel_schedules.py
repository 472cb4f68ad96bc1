"""A kernel's tile schedule is a function of its shapes alone, decided in the
kernel's own module: no environment variable and no file moves it.

The shapes are the ones the benchmark's cells and ``chip_smoke.py`` run
(PERF.md section 4): sequences of 1024 and 2048, 16 heads of 64 and of 128,
the serving cell's 32 slots x table of 128 x pages of 16, the smoke run's
generation engine (8 slots, 8 heads of 128) and its int8 MLP (256-512-512-128
at batch 16). The expected values are what every run the ledger holds used.
Read from the traced kernel call where the grid is the schedule (flash,
paged), from ``resolve_blocks`` where the router asks it first (int8).
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from analytics_zoo_tpu.analysis.graphlint import walk_eqns
from analytics_zoo_tpu.common.compile_cache import CHECKOUT
from analytics_zoo_tpu.ops import flash_attention as fa
from analytics_zoo_tpu.ops import int8_fused
from analytics_zoo_tpu.ops.paged_attention import paged_attention

pytestmark = pytest.mark.pallas

_BF16 = jnp.bfloat16


def _grids(fn, *shapes):
    """``{kernel name: grid}`` of the pallas calls ``fn`` traces to."""
    jaxpr = jax.make_jaxpr(fn)(*(jax.ShapeDtypeStruct(s, d)
                                 for s, d in shapes))
    return {site.eqn.params["name"]:
            tuple(site.eqn.params["grid_mapping"].grid)
            for site in walk_eqns(jaxpr.jaxpr)
            if site.eqn.primitive.name == "pallas_call"}


def _flash(t, heads, d, batch=2):
    """(tiles, forward grid) of a causal flash call at (batch, t, heads, d):
    the grid is (batch x heads, q tiles, k tiles)."""
    x = ((batch, t, heads, d), _BF16)
    grid = _grids(lambda q, k, v: fa.flash_attention(
        q, k, v, True, None, None, True), x, x, x)["zoo_flash_fwd"]
    return fa.resolve_blocks(t, t), grid


def _paged(slots, table, page, heads, d, q_len=1):
    """Grid of a paged-attention call: (slot, head-block, query-tile), so
    ``heads // grid[1]`` heads a program."""
    pool = ((slots * table // 2 + 1, page, heads, d), _BF16)
    return _grids(
        lambda q, k, v, t, n: paged_attention(q, k, v, t, n, page_size=page,
                                              interpret=True),
        ((slots, q_len, heads, d), _BF16), pool, pool,
        ((slots, table), jnp.int32), ((slots,), jnp.int32)
    )["zoo_paged_attention"]


#: case -> (how it is read, what every measured run used)
SCHEDULES = {
    # train-gpt2m-1k's widths (auto takes full attention there; an explicit
    # flash call at them gets these tiles)
    "flash-t1024-16x64": (lambda: _flash(1024, 16, 64),
                          ((512, 512), (32, 2, 2))),
    # train-cgpt-zero1-x4 and the 2048 prefill bucket of gen-chat-steady
    "flash-t2048-16x128": (lambda: _flash(2048, 16, 128),
                           ((512, 512), (32, 4, 4))),
    # what the forward's 16 grid steps of a head do at those tiles, by the
    # kernel's own predicate (PR 42): (skipped, working). A causal call masks
    # every working tile: masking only the four the diagonal crosses was
    # measured on the v5e and refused (PERF.md section 6, PR 42)
    "flash-plan-t2048-512x512": (
        lambda: fa.forward_tile_plan(2048, 2048, 512, 512, True), (6, 10)),
    "flash-plan-t1024-512x512": (
        lambda: fa.forward_tile_plan(1024, 1024, 512, 512, True), (1, 3)),
    # a ring's off-diagonal step: nothing skipped
    "flash-plan-t2048-dense": (
        lambda: fa.forward_tile_plan(2048, 2048, 512, 512, False), (0, 16)),
    # gen-chat-steady's decode step: all 16 heads in one program
    "paged-32x128x16-16x128": (lambda: _paged(32, 128, 16, 16, 128),
                               (32, 1, 1)),
    "paged-32x128x16-16x64": (lambda: _paged(32, 128, 16, 16, 64),
                              (32, 1, 1)),
    # chip_smoke.py's generation engine, decode and a 4-token verify step
    "paged-8x128x16-8x128": (lambda: _paged(8, 128, 16, 8, 128), (8, 1, 1)),
    "paged-8x128x16-8x128-q4": (lambda: _paged(8, 128, 16, 8, 128, q_len=4),
                                (8, 1, 1)),
    # chip_smoke.py's int8 MLP: (batch, out, in) of its three Dense layers
    "int8-16x512x256": (lambda: int8_fused.resolve_blocks(16, 512, 256),
                        (16, 256, 256)),
    "int8-16x512x512": (lambda: int8_fused.resolve_blocks(16, 512, 512),
                        (16, 256, 512)),
    "int8-16x128x512": (lambda: int8_fused.resolve_blocks(16, 128, 512),
                        (16, 128, 512)),
}


@pytest.mark.parametrize("case", sorted(SCHEDULES))
def test_schedule_at_the_measured_shapes(case):
    read, want = SCHEDULES[case]
    assert read() == want


def _retired_cache():
    """A ``.zoo_tuning.json`` as the retired autotuner wrote it, naming other
    tiles for every case above, on the CPU's device kind and the v5e's."""
    table = {
        "flash": {f"{t}x{t}/bfloat16": {"block_q": 128, "block_k": 256}
                  for t in (1024, 2048)},
        "paged": {f"{q}x128x16x{h}x{d}/bfloat16/walk": {"block_h": 2}
                  for q, h, d in ((1, 16, 128), (1, 16, 64), (1, 8, 128),
                                  (4, 8, 128))},
        "int8_matmul": {f"16x{n}x{k}/float32":
                        {"block_m": 8, "block_n": 128, "block_k": 128}
                        for n, k in ((512, 256), (512, 512), (128, 512))},
    }
    return {"version": 1, "devices": {"cpu-interpret": table,
                                      "TPU v5 lite": table}}


#: the six tile names the kernels read until PR 28, each set to a tile the
#: shapes above would accept
RETIRED_ENV = {
    "ZOO_FLASH_BLOCK_Q": "128", "ZOO_FLASH_BLOCK_K": "256",
    "ZOO_PAGED_BLOCK_H": "2",
    "ZOO_INT8_BLOCK_M": "8", "ZOO_INT8_BLOCK_N": "128",
    "ZOO_INT8_BLOCK_K": "128",
}


@pytest.mark.parametrize("disturbance", sorted(RETIRED_ENV) + ["cache-file"])
def test_schedule_is_a_function_of_shapes_alone(disturbance, monkeypatch):
    """One forgotten sweep on a chip machine used to change the tiles the
    benchmark's executables compiled with, with nothing in git or in the
    run's output to say so. Nothing outside the call's shapes does now."""
    path = os.path.join(CHECKOUT, ".zoo_tuning.json")   # where it lay
    if disturbance == "cache-file":
        with open(path, "w") as f:
            json.dump(_retired_cache(), f)
    else:
        monkeypatch.setenv(disturbance, RETIRED_ENV[disturbance])
    try:
        got = {case: read() for case, (read, _) in SCHEDULES.items()}
    finally:
        if disturbance == "cache-file":
            os.remove(path)
    assert got == {case: want for case, (_, want) in SCHEDULES.items()}
