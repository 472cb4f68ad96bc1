"""Transformer-LM MFU sweep — batch size × flash tile sizes, one table.

On the chip (one process holds it, so run nothing beside this):

    python dev/mfu_sweep.py                 # default grid
    python dev/mfu_sweep.py --trace         # + xprof trace of the best point

Reuses bench.run_transformer_mfu for the measurement (identical FLOP
accounting and timing discipline) and sweeps the flash-attention tile sizes
via env knobs read by the model layer. Each point costs one compile
(persistent cache makes re-runs cheap). MFU is a device number: off a TPU the
script exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))


def main() -> int:
    ap = argparse.ArgumentParser(description="MFU sweep")
    ap.add_argument("--batches", type=int, nargs="*", default=[4, 8, 16, 32])
    ap.add_argument("--blocks", type=str, nargs="*",
                    default=["128x128", "256x128", "256x256", "512x256"],
                    help="flash block_q x block_k pairs")
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--n-block", type=int, default=8)
    ap.add_argument("--trace", action="store_true",
                    help="xprof-trace the winning config")
    ap.add_argument("--out", default="MFU_SWEEP.json")
    args = ap.parse_args()

    from bench import _require_tpu, run_transformer_mfu

    _require_tpu("dev/mfu_sweep.py")

    rows, best = [], None
    for blocks in args.blocks:
        bq, bk = (int(v) for v in blocks.split("x"))
        if args.seq_len % bq or args.seq_len % bk:
            # a non-tiling pair cannot run the kernel
            print(f"[sweep] skip blocks={blocks}: seq_len {args.seq_len} "
                  f"not divisible", file=sys.stderr)
            continue
        # the attention layer reads these at trace time
        os.environ["ZOO_FLASH_BLOCK_Q"] = str(bq)
        os.environ["ZOO_FLASH_BLOCK_K"] = str(bk)
        for b in args.batches:
            try:
                r = run_transformer_mfu(seq_len=args.seq_len, batch=b,
                                        hidden=args.hidden,
                                        n_block=args.n_block)
            except Exception as e:
                print(f"[sweep] b={b} blocks={blocks} failed: {e}",
                      file=sys.stderr)
                continue
            row = {"batch": b, "block_q": bq, "block_k": bk,
                   "remat": r["remat"], "mfu": r["mfu"],
                   "tokens_per_sec": r["tokens_per_sec"],
                   "device": r["device_kind"]}
            rows.append(row)
            print(f"b={b:>3} blocks={blocks:>8} remat={int(r['remat'])} "
                  f"mfu={r['mfu']:.4f} tok/s={r['tokens_per_sec']:,.0f}")
            if best is None or r["mfu"] > best["mfu"]:
                best = row

    if not rows:
        print("[sweep] nothing measured", file=sys.stderr)
        return 1
    result = {"rows": rows, "best": best,
              "config": {"seq_len": args.seq_len, "hidden": args.hidden,
                         "n_block": args.n_block}}
    with open(args.out + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(args.out + ".tmp", args.out)
    print(f"best: {best} -> {args.out}")

    if args.trace and best:
        from analytics_zoo_tpu.common.profiling import xprof_trace

        os.environ["ZOO_FLASH_BLOCK_Q"] = str(best["block_q"])
        os.environ["ZOO_FLASH_BLOCK_K"] = str(best["block_k"])
        with xprof_trace("/tmp/zoo_mfu_trace"):
            run_transformer_mfu(seq_len=args.seq_len, batch=best["batch"],
                                hidden=args.hidden, n_block=args.n_block)
        print("trace written to /tmp/zoo_mfu_trace")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
