#!/usr/bin/env python3
"""Time the two single-device attention routes against each other on the chip.

``python scripts/attention_routes.py --out chiprun_out/routes.json``
times ``ops.flash_attention.flash_attention`` against
``ops.attention.full_attention`` (bf16, the tree's own functions at their
default tiles) forward alone and forward plus backward, at every sequence
length and (batch, heads, head size) asked for. A time is the device time of
one run of the jitted call, the layout transposes round the kernel included,
read from the profiler's ``XLA Modules`` line; the median of ``--runs`` runs.
The table it prints is what ``ops.attention.prefer_flash_single_device``'s
thresholds are taken from (``PERF.md`` section 6, PR 44). The trace is read
with the benchmark's reader (``benchmark/readers/xplane.py``).
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import shutil
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LENGTHS = (256, 512, 1024, 2048)
SHAPES = ((4, 16, 64), (8, 16, 64), (1, 16, 128), (2, 16, 128))
#: (batch, heads, head size) also timed without the causal mask
NON_CAUSAL = ((4, 16, 64),)


def _case(route: str, backward: bool, causal: bool, t: int, shape):
    """The jitted call of one cell of the table, the name under which its
    runs are found in the trace, and the cell's row without its times."""
    import jax

    from analytics_zoo_tpu.ops.attention import full_attention
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    if route == "flash":
        attend = lambda q, k, v: flash_attention(q, k, v, causal)
    else:
        attend = lambda q, k, v: full_attention(q, k, v, causal=causal)

    def forward(q, k, v, g):
        return attend(q, k, v)

    def both(q, k, v, g):
        out, pull = jax.vjp(attend, q, k, v)
        return (out,) + pull(g)

    fn = both if backward else forward
    b, h, d = shape
    fn.__name__ = (f"route_{route}_{'fb' if backward else 'f'}_"
                   f"{'c' if causal else 'n'}_t{t}_b{b}_h{h}_d{d}")
    row = {"route": route, "backward": backward, "causal": causal, "t": t,
           "batch": b, "heads": h, "head_size": d}
    return jax.jit(fn), fn.__name__, row


def measure(lengths, shapes, non_causal, runs: int):
    import jax
    import jax.numpy as jnp

    from benchmark.readers import xplane

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"attention_routes times a chip, found {device}")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    rows = []
    for shape in shapes:
        b, h, d = shape
        for t in lengths:
            keys = jax.random.split(jax.random.PRNGKey(t + b), 4)
            args = [jax.random.normal(key, (b, t, h, d), jnp.bfloat16)
                    for key in keys]
            cases = [_case(route, backward, causal, t, shape)
                     for causal in ((True, False) if shape in non_causal
                                    else (True,))
                     for backward in (True, False)
                     for route in ("flash", "full")]
            for fn, _, _ in cases:                      # compiles
                jax.block_until_ready(fn(*args))
            tdir = tempfile.mkdtemp(prefix="routes_")
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                for fn, _, _ in cases:
                    for _ in range(runs):
                        jax.block_until_ready(fn(*args))
            finally:
                jax.profiler.stop_trace()
            (pb,) = glob.glob(os.path.join(tdir,
                                           "plugins/profile/*/*.xplane.pb"))
            trace = xplane.load(pb)
            shutil.rmtree(tdir)
            seen = collections.defaultdict(list)
            for run in trace.devices[0].modules:
                seen[run.name].append((run.end - run.start) * 1e6)
            for _, name, row in cases:
                times = seen["jit_" + name]
                if len(times) != runs:
                    raise SystemExit(f"{name}: {len(times)} runs in the "
                                     f"trace, {runs} made")
                rows.append(dict(row, median_us=statistics.median(times),
                                 min_us=min(times), max_us=max(times)))
            print(f"timed {shape} t={t}", file=sys.stderr, flush=True)
    return {"device_kind": device.device_kind, "platform": device.platform,
            "jax": jax.__version__, "runs": runs, "rows": rows}


def table(result) -> str:
    """Markdown: a line a shape and pass, flash / full (full over flash) by
    sequence length."""
    cell = {(r["backward"], r["causal"], r["batch"], r["heads"],
             r["head_size"], r["t"], r["route"]): r["median_us"]
            for r in result["rows"]}
    lengths = sorted({r["t"] for r in result["rows"]})
    lines = ["| pass | mask | (B, H, D) | "
             + " | ".join(f"T={t}: flash / full us (full:flash)"
                          for t in lengths) + " |",
             "|---|---|---|" + "---|" * len(lengths)]
    for key in sorted({k[:5] for k in cell}, key=lambda k: (not k[0], not k[1],
                                                            k[4], k[2])):
        backward, causal, b, h, d = key
        cols = []
        for t in lengths:
            flash, full = cell[key + (t, "flash")], cell[key + (t, "full")]
            cols.append(f"{flash:.1f} / {full:.1f} ({full / flash:.2f})")
        lines.append(f"| {'fwd+bwd' if backward else 'fwd'} | "
                     f"{'causal' if causal else 'none'} | ({b}, {h}, {d}) | "
                     + " | ".join(cols) + " |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="write the readings here as JSON")
    ap.add_argument("--runs", type=int, default=12)
    ap.add_argument("--lengths", type=int, nargs="+", default=list(LENGTHS))
    ap.add_argument("--shapes", nargs="+", metavar="B,H,D",
                    type=lambda s: tuple(int(n) for n in s.split(",")),
                    default=list(SHAPES))
    args = ap.parse_args(argv)
    result = measure(args.lengths, args.shapes, NON_CAUSAL, args.runs)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(table(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
