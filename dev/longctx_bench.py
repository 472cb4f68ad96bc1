"""Long-context attention benchmark — pallas flash vs XLA full attention.

The long-context pillar (SURVEY §5.7/§7: ring & Ulysses sequence parallelism
with a blockwise pallas kernel inside each shard) is oracle-tested on CPU
meshes; this tool captures the single-chip half of the scaling story on the
real device: fwd+bwd attention time and the longest sequence each strategy
can run before HBM runs out. Flash keeps O(block) score memory, so it should
extend to sequence lengths where materializing the (H, T, T) score tensor
OOMs, at comparable or better step time.

    python dev/longctx_bench.py                   # default ladder

Writes LONGCTX_BENCH.json (one row per (strategy, seq_len)).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))


def _is_oom(e: Exception) -> bool:
    msg = str(e).lower()
    return ("resource_exhausted" in msg or "out of memory" in msg
            or "allocation" in msg)


def measure(strategy: str, seq_len: int, n_head: int, head_dim: int,
            reps: int = 20) -> dict:
    """Fwd+bwd wall time of one attention call at (1, seq_len, n_head, head_dim).

    Iterations chain inside one jitted fori_loop (carry feeds q) so the
    number is pure device time, with no per-call dispatch or sync in it.
    """
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops.attention import full_attention
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    shape = (1, seq_len, n_head, head_dim)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)

    def one(qi):
        if strategy == "flash":
            o = flash_attention(qi, k, v, True)
        else:
            o = full_attention(qi, k, v, causal=True)
        return o

    def loss(qi):
        return jnp.sum(one(qi).astype(jnp.float32) ** 2)

    @jax.jit
    def loop(q):
        def body(_, carry):
            qc, acc = carry
            l, g = jax.value_and_grad(loss)(qc)
            eps = (l * 1e-30).astype(jnp.bfloat16)
            return (q + eps, acc + l * 1e-30)

        _, acc = jax.lax.fori_loop(0, reps, body, (q, jnp.float32(0)))
        return acc

    float(loop(q))                       # compile + warm
    t0 = time.perf_counter()
    float(loop(q))
    dt = (time.perf_counter() - t0) / reps
    # causal fwd+bwd attention flops: 3 matmuls bwd + 2 fwd ≈ 2.5 × 2·2·T²·H·D
    flops = 2.5 * 4 * seq_len * seq_len * n_head * head_dim / 2  # /2 causal
    return {
        "strategy": strategy,
        "seq_len": seq_len,
        "ms_per_iter": round(dt * 1e3, 3),
        "tokens_per_sec": round(seq_len / dt, 1),
        "attn_tflops": round(flops / dt / 1e12, 2),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description="long-context attention bench")
    ap.add_argument("--seq-lens", type=int, nargs="*",
                    default=[4096, 8192, 16384, 32768, 65536])
    ap.add_argument("--n-head", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="LONGCTX_BENCH.json")
    args = ap.parse_args()

    from bench import _require_tpu, enable_compile_cache

    _require_tpu("dev/longctx_bench.py")
    enable_compile_cache()
    import jax

    rows = []
    dead = set()
    for strategy in ("flash", "full"):
        for s in args.seq_lens:
            if strategy in dead:
                break
            try:
                r = measure(strategy, s, args.n_head, args.head_dim,
                            args.reps)
            except Exception as e:
                kind = "oom" if _is_oom(e) else "error"
                rows.append({"strategy": strategy, "seq_len": s, kind: True,
                             "detail": str(e)[:200]})
                print(f"{strategy:>5} T={s:>6}: {kind}", file=sys.stderr)
                if kind == "oom":
                    dead.add(strategy)   # longer seqs can only OOM harder
                continue
            rows.append(r)
            print(f"{strategy:>5} T={r['seq_len']:>6}: {r['ms_per_iter']:>9} "
                  f"ms/iter  {r['attn_tflops']:>6} TF")

    result = {"rows": rows,
              "config": {"n_head": args.n_head, "head_dim": args.head_dim,
                         "batch": 1, "causal": True,
                         "device": str(jax.devices()[0].device_kind)},
              "note": ("fwd+bwd causal self-attention, batch 1, bf16, device-"
                       "resident timed loop. flash = pallas blockwise kernel "
                       "(O(block) score memory); full = XLA attention "
                       "materializing (H, T, T) scores.")}
    with open(args.out + ".tmp", "w") as f:
        json.dump(result, f, indent=1)
    os.replace(args.out + ".tmp", args.out)
    print(f"-> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
