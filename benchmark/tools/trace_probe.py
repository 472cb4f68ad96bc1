#!/usr/bin/env python3
"""Look at a profiler trace by hand: planes, lines, event names and stats.

``python benchmark/tools/trace_probe.py <file.xplane.pb>`` prints the
structure of a recorded trace. ``--record <dir>`` first records two small ones
on the chip it is started on (a served prefill and a few decode steps through
``ContinuousBatcher``, and a few ``Estimator.fit`` steps at a sequence length
where ``auto`` takes the flash kernels) and writes them, with their dumps, into
``<dir>``. The recorded traces are what ``benchmark/readers/xplane.py`` is
checked against.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def dump(path: str, out) -> None:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    print(f"FILE {path} {os.path.getsize(path)} bytes", file=out)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}", file=out)
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r} events={len(events)} "
                  f"from={t0:.0f} to={t1:.0f}", file=out)
            by_name = collections.defaultdict(lambda: [0, 0.0, None])
            for e in events:
                rec = by_name[e.name]
                rec[0] += 1
                rec[1] += e.duration_ns
                if rec[2] is None:
                    rec[2] = dict(e.stats)
            top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:25]
            for name, (n, dur, stats) in top:
                stats = {k: (str(v)[:160]) for k, v in stats.items()}
                print(f"    {n:6d} x {dur / 1e3:12.1f} us  {name[:120]!r}  "
                      f"{stats}", file=out)


def record(out_dir: str) -> None:
    import jax
    import numpy as np

    from analytics_zoo_tpu.common import (MeshConfig, PrecisionConfig,
                                          RuntimeConfig, TrainConfig,
                                          init_zoo_context)
    from analytics_zoo_tpu.engine import Estimator
    from analytics_zoo_tpu.models.transformer import TransformerLM, lm_loss
    from analytics_zoo_tpu.nn.optimizers import Adam
    from analytics_zoo_tpu.serving.generation import ContinuousBatcher

    os.makedirs(out_dir, exist_ok=True)
    ctx = init_zoo_context(RuntimeConfig(
        mesh=MeshConfig(dp=0),
        precision=PrecisionConfig(compute_dtype="bfloat16")))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    model = TransformerLM(vocab=1024, hidden_size=256, n_block=2, n_head=2,
                          seq_len=2048)
    rng = np.random.default_rng(0)

    def traced(name, fn):
        tdir = os.path.join(out_dir, name + "_trace")
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (pb,) = glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb"))
        kept = os.path.join(out_dir, name + ".xplane.pb")
        shutil.copy(pb, kept)
        shutil.rmtree(tdir)
        with open(os.path.join(out_dir, name + ".txt"), "w") as f:
            dump(kept, f)

    params, _ = model.build(jax.random.PRNGKey(0))
    batcher = ContinuousBatcher(model, params, n_slots=4, page_size=16,
                                max_seq_len=2048)
    prompts = [rng.integers(1, 1024, size=n).astype(np.int32)
               for n in (100, 1500)]

    def serve():
        handles = [batcher.submit(p, max_new_tokens=6) for p in prompts]
        for h in handles:
            h.result(timeout_s=600)

    serve()                                     # compiles
    traced("serve", serve)
    print("serve stats", batcher.stats(), flush=True)
    batcher.close()

    est = Estimator(model, optimizer=Adam(lr=1e-3), loss=lm_loss,
                    mesh=ctx.mesh, config=TrainConfig())
    x = rng.integers(0, 1024, size=(2 * 3, 2048)).astype("int32")
    data = (x, np.roll(x, -1, axis=1))
    est.fit(data, batch_size=2, epochs=1)       # compiles
    traced("train", lambda: est.fit(data, batch_size=2, epochs=2))
    print("devices", jax.devices(), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("files", nargs="*")
    ap.add_argument("--record", metavar="DIR")
    args = ap.parse_args(argv)
    if args.record:
        record(args.record)
    for path in args.files:
        dump(path, sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
