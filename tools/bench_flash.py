#!/usr/bin/env python
"""Flash-attention kernel benchmark.

Runs on the chip only (fails off TPU): forward-only and full fwd+bwd
(``jax.grad`` through the custom_vjp backward kernels) at the ladder
geometry [B=4, S, H=8, D=64] bf16, for full / causal / sliding-window
attention, optionally sweeping block sizes.

Timing is TRACE-BASED (round 4): each config runs 3× under
``jax.profiler``, and the reported milliseconds are the Pallas kernels'
own device time parsed from the xplane (xprof ``op_profile``), which no
per-dispatch host cost can dilute. The wall column is still printed for
context.

Usage:
    python tools/bench_flash.py                  # standard table
    python tools/bench_flash.py --blocks 512 1024  # block-size sweep
    python tools/bench_flash.py --seqs 8192 16384

TF/s columns use the ALGORITHMIC flop counts (4·B·H·S²·D forward;
3.5× that for fwd+bwd — dQ pass + dK/dV pass with recompute), so
causal/window rows show their *speedup* rather than inflated rates.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _kernel_ms(trace_dir: str, reps: int) -> float:
    """Sum the tpu_custom_call (Pallas) raw times in an xplane trace."""
    from xprof.convert import raw_to_tool_data as rtd

    pbs = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data, _ = rtd.xspace_to_tool_data([pbs[0]], "op_profile", {})
    tree = json.loads(data.decode() if isinstance(data, bytes) else data)
    total_ps = 0.0

    def walk(node):
        nonlocal total_ps
        xla = node.get("xla") or {}
        m = node.get("metrics", {})
        if xla.get("category") == "custom-call" and \
                "tpu_custom_call" in xla.get("expression", ""):
            total_ps += m.get("rawTime", 0)
        for ch in node.get("children", []):
            walk(ch)

    walk(tree.get("byProgram", {}))
    return total_ps / 1e9 / reps


def bench(fn, *args, reps: int = 3, tag: str = "b") -> tuple[float, float]:
    """→ (kernel_ms, wall_ms_per_call)."""
    s = fn(*args)
    jax.device_get(s)                    # compile + warm
    d = f"/tmp/bench_flash_trace_{tag}"
    shutil.rmtree(d, ignore_errors=True)
    t0 = time.perf_counter()
    jax.profiler.start_trace(d)
    for _ in range(reps):
        s = fn(*args)
    jax.device_get(s)
    jax.profiler.stop_trace()
    wall = (time.perf_counter() - t0) / reps
    km = _kernel_ms(d, reps)
    shutil.rmtree(d, ignore_errors=True)
    return km, wall * 1e3


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seqs", type=int, nargs="+",
                   default=[4096, 8192, 16384])
    p.add_argument("--blocks", type=int, nargs="+", default=[None],
                   help="explicit block sizes to sweep (default: auto)")
    p.add_argument("--windows", type=int, nargs="+", default=[1024, 4096])
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head_dim", type=int, default=64)
    args = p.parse_args()

    from bench import device_stamp
    stamp = device_stamp()  # fails off TPU / on an unknown device_kind

    from dml_cnn_cifar10_tpu.ops import flash_attention as fa

    B, H, D = args.batch, args.heads, args.head_dim
    key = jax.random.PRNGKey(0)

    def grad_fn(blk, **kw):
        bkw = {} if blk is None else dict(block_q=blk, block_k=blk)

        @jax.jit
        def g(q, k, v):
            gr = jax.grad(lambda q, k, v: jnp.sum(
                fa.flash_attention(q, k, v, **bkw, **kw)
                .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)
            return sum(jnp.sum(t.astype(jnp.float32)) for t in gr)
        return g

    def fwd_fn(blk, **kw):
        bkw = {} if blk is None else dict(block_q=blk, block_k=blk)
        return jax.jit(lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, **bkw, **kw)
            .astype(jnp.float32)))

    print(f"[B={B}, S, H={H}, D={D}] bf16 on {stamp}; "
          f"kernel ms from xplane over {args.reps} reps\n")
    print("| S | block | variant | fwd ms | fwd+bwd ms | fwd+bwd wall ms "
          "| fwd+bwd TF/s | vs full |")
    print("|---|---|---|---|---|---|---|---|")
    for S in args.seqs:
        q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
                   for kk in jax.random.split(key, 3))
        algo = 3.5 * 4 * B * H * S * S * D
        for blk in args.blocks:
            variants = [("full", {})] + [("causal", dict(causal=True))] + [
                (f"W={w}", dict(window=w)) for w in args.windows
                if w < S] + [
                (f"W={w} causal", dict(window=w, causal=True))
                for w in args.windows if w < S]
            base = None
            for name, kw in variants:
                dt_f, _ = bench(fwd_fn(blk, **kw), q, k, v,
                                reps=args.reps, tag="f")
                dt, wall = bench(grad_fn(blk, **kw), q, k, v,
                                 reps=args.reps, tag="g")
                base = dt if base is None else base
                bs = "auto" if blk is None else str(blk)
                print(f"| {S} | {bs} | {name} | {dt_f:.2f} | "
                      f"{dt:.2f} | {wall:.1f} | "
                      f"{algo / (dt / 1e3) / 1e12:.1f} | "
                      f"{base / dt:.2f}x |", flush=True)


if __name__ == "__main__":
    main()
