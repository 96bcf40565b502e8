"""Paper Fig. 5 / App. B analog: SHiRA scatter-load vs LoRA fuse latency.

For weight dims 1024..4096 (paper uses up to 8192), measures wall-clock on
the default backend:
  * SHiRA switch: scatter-add of 1% packed updates, through XLA's scatter
    (``scatter_packed_add``) and through the switch's tile kernel
    (``ops.scatter_apply``: bucketing by weight block plus the one-hot
    tile pass; the Pallas interpreter off the TPU, so only a chip run
    times the kernel),
  * LoRA fuse: W + A@B at rank 64 (the paper's LVM rank),
  * pack I/O: loading the adapter from a format-v2 file via the
    ``repro.hub`` store, f32 and int8 (the cold-start path: an evicted
    tenant's first request pays load + scatter),
and derives the TPU-side byte model: adapter bytes moved vs full-weight
rewrite + GEMM FLOPs (reported as model terms since this container has no
TPU clock).
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

import _emit
from repro.core import masks as M
from repro.core.adapters import AdapterPack
from repro.hub import load_pack, save_pack
from repro.kernels import ops

RANK = 64
SPARSITY = 0.99


def timed(fn, *args, reps=5):
    fn(*args).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH", help="write BENCH_rapid_switching.json "
                    "(or PATH) with the _emit schema")
    args = ap.parse_args()
    rows = []
    interpret = jax.default_backend() != "tpu"
    print("dim,shira_scatter_ms,shira_kernel_ms,lora_fuse_ms,speedup,"
          "shira_bytes_mb,lora_bytes_mb,lora_gemm_gflop,"
          "pack_load_f32_ms,pack_load_int8_ms,int8_shrink")
    rng = np.random.RandomState(0)
    tmp = tempfile.mkdtemp(prefix="rs-bench-")
    for dim in (1024, 2048, 4096):
        w = jnp.asarray(rng.randn(dim, dim), jnp.float32)
        k = int((1 - SPARSITY) * dim * dim)
        idx = jnp.asarray(np.sort(rng.choice(dim * dim, k, replace=False)),
                          jnp.int32)
        vals = jnp.asarray(rng.randn(k), jnp.float32)
        a = jnp.asarray(rng.randn(dim, RANK), jnp.float32)
        b = jnp.asarray(rng.randn(RANK, dim), jnp.float32)

        scatter = jax.jit(lambda w, i, v: M.scatter_packed_add(
            w[None], i[None], v[None])[0])
        kernel = jax.jit(lambda w, i, v: ops.scatter_apply(
            w, [(i, v)], [1.0], interpret=interpret))
        fuse = jax.jit(lambda w, a, b: w + a @ b)

        t_s = timed(scatter, w, idx, vals) * 1e3
        t_k = timed(kernel, w, idx, vals, reps=1 if interpret else 5) * 1e3
        t_f = timed(fuse, w, a, b) * 1e3

        shira_mb = k * 8 / 1e6                      # idx + val
        lora_mb = (2 * dim * RANK + dim * dim) / 1e6 * 4  # A,B in + W rewrite
        gemm_gflop = 2 * RANK * dim * dim / 1e9

        # cold-start pack I/O: format-v2 file -> usable AdapterPack
        pack = AdapterPack(f"d{dim}", {"w": (idx, vals)})
        t_io = {}
        for mode in ("f32", "int8"):
            f = save_pack(pack, os.path.join(tmp, f"d{dim}_{mode}.shpk"),
                          values=mode)
            t0 = time.perf_counter()
            loaded = load_pack(f)
            t_io[mode] = (time.perf_counter() - t0) * 1e3
        q = load_pack(os.path.join(tmp, f"d{dim}_int8.shpk"),
                      dequantize=False)
        print(f"{dim},{t_s:.2f},{t_k:.2f},{t_f:.2f},{t_f / t_s:.2f},"
              f"{shira_mb:.2f},{lora_mb:.2f},{gemm_gflop:.2f},"
              f"{t_io['f32']:.2f},{t_io['int8']:.2f},"
              f"{pack.nbytes() / q.nbytes():.1f}x")
        rows.append({"dim": dim, "shira_scatter_ms": t_s,
                     "shira_kernel_ms": t_k,
                     "lora_fuse_ms": t_f,
                     "pack_load_f32_ms": t_io["f32"],
                     "pack_load_int8_ms": t_io["int8"],
                     "int8_shrink": pack.nbytes() / q.nbytes()})

    if args.json is not None:
        top = rows[-1]            # the largest dim anchors the gate
        res = _emit.result(
            "rapid_switching", f"dense-{top['dim']}",
            metrics={
                "switches_per_s": 1e3 / top["shira_scatter_ms"],
                "switch_latency_ms": top["shira_scatter_ms"],
                "lora_fuse_ms": top["lora_fuse_ms"],
                "pack_load_int8_ms": top["pack_load_int8_ms"],
                "int8_shrink": top["int8_shrink"],
            },
            meta={"sparsity": SPARSITY, "rank": RANK, "dims": rows})
        print(f"wrote {_emit.emit(res, args.json or None)}")


if __name__ == "__main__":
    main()
