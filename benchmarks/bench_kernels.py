"""Kernel-level A/B (paper Fig. 5, "implementation choices"): the XLA chunked
path vs the Pallas kernel in interpret mode (numerical parity + call cost),
plus the paper's HEADLINE A/B — dense-bias attention vs FlashBias factored
bias — emitted as ``BENCH_kernels.json`` at the repo root (the kernel half
of the perf trajectory, next to ``BENCH_serve.json``).

The dense-vs-factored A/B is a SWEEP over sequence lengths, and the
headline (the ``dense_vs_factored`` entry the CI gate reads) is its
LARGEST, paper-scale point: at tiny N the factored path's extra rank-R
matmul per tile dominates the saved Θ(N·M) bias IO and the factored path
legitimately *loses* (the committed artifact once reported speedup 0.80 at
N=128 as if it were the result) — FlashBias's claim is about the regime
where the bias matrix is the traffic, which is exactly where serving runs.
The small-N points stay in ``dense_vs_factored_sweep`` so the crossover is
visible, not hidden.

interpret=True runs the kernel body in Python — its wall time is NOT TPU
performance; the number that matters there is allclose parity and the block
configuration that the TPU deployment will use (block_q=block_k=128). The
dense-vs-factored A/B times two fully-jitted XLA paths of the SAME workload,
so its ratio is a meaningful relative trend even on CPU
(benchmarks/common.py caveat).

    PYTHONPATH=src python -m benchmarks.bench_kernels [--smoke] [--out PATH]
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, time_fn
from repro.core import bias as bias_mod
from repro.kernels import ops, ref

DEFAULT_OUT = "BENCH_kernels.json"


def headline_point(sweep: list) -> dict:
    """The gated ``dense_vs_factored`` headline: the LARGEST-seq sweep
    point (the paper-scale, bias-IO-dominated regime). Factored-bias
    attention legitimately loses at tiny N, so headlining a small-N point
    would gate the wrong regime — keep this the single source of truth
    for headline selection (unit-tested in tests/test_check_bench.py)."""
    return max(sweep, key=lambda pt: pt["seq_len"])


def _dense_vs_factored(n: int, rank: int, chunk: int = 128) -> dict:
    """Same attention workload, dense (H, N, N) bias vs rank-R factors.

    Both sides run the SAME chunked flash path at the SAME chunk size —
    only the bias representation differs (a streamed (H, N, N) slab vs
    rank-R factors folded into the QK matmul per Eq. 3). The old bench
    compared dense-at-chunk-128 against the factored path's default
    chunk-512 dispatch, so its ratio mixed chunking effects into the bias
    A/B and under-reported the factored win. chunk=128 mirrors the TPU
    kernel's block_k. The dense bias is materialized OUTSIDE the timed
    region (charitable to the baseline: ALiBi-style biases could be
    cached), so the measured gap is pure per-call bias traffic/compute.
    """
    b, h, d = 1, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (b, n, h, d))
    k = jax.random.normal(ks[1], (b, n, h, d))
    v = jax.random.normal(ks[2], (b, n, h, d))
    pq = jax.random.normal(ks[3], (b, n, h, rank))
    pk = jax.random.normal(ks[4], (b, n, h, rank))
    dense = jnp.einsum("bnhr,bmhr->bhnm", pq, pk)     # materialized bias

    from repro.core.attention import MaskSpec, attention
    dense_fn = jax.jit(lambda q, k, v, bias: attention(
        q, k, v, mask=MaskSpec("causal"), bias=bias, impl="chunked",
        chunk_size=chunk))
    fact_fn = jax.jit(lambda q, k, v, pq, pk: attention(
        q, k, v, mask=MaskSpec("causal"), phi_q=pq, phi_k=pk,
        impl="chunked", chunk_size=chunk))

    t_dense = time_fn(dense_fn, q, k, v, dense)
    t_fact = time_fn(fact_fn, q, k, v, pq, pk)
    err = float(jnp.abs(dense_fn(q, k, v, dense)
                        - fact_fn(q, k, v, pq, pk)).max())
    return {"seq_len": n, "heads": h, "head_dim": d, "rank": rank,
            "chunk": chunk,
            "dense_bias_us": t_dense * 1e6,
            "factored_bias_us": t_fact * 1e6,
            "speedup": t_dense / max(t_fact, 1e-12),
            "max_abs_err": err}


def run(out_path: str = DEFAULT_OUT, smoke: bool = False):
    rows = []
    n = 128 if smoke else 256
    b, h, kvh, d = 1, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, n, h, d))
    k = jax.random.normal(ks[1], (b, n, kvh, d))
    v = jax.random.normal(ks[2], (b, n, kvh, d))
    slopes = bias_mod.alibi_slopes(h)

    xla_fn = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, slopes=slopes, mask_kind="causal", impl="xla"))
    t_xla = time_fn(xla_fn, q, k, v)
    rows.append(Row("fig5_xla_chunked_alibi", t_xla * 1e6,
                    "training-path impl (paper: SDPA)"))

    out_pallas = ops.flash_attention(q, k, v, slopes=slopes,
                                     mask_kind="causal",
                                     impl="pallas_interpret",
                                     block_q=128, block_k=128)
    err = float(jnp.abs(out_pallas - xla_fn(q, k, v)).max())
    rows.append(Row("fig5_pallas_parity", 0.0,
                    f"max_err={err:.2e} (blocks 128x128, TPU target)"))

    # decode kernel parity at production block size
    s = 256 if smoke else 512
    kc = jax.random.normal(ks[1], (2, s, kvh, d))
    vc = jax.random.normal(ks[2], (2, s, kvh, d))
    q1 = jax.random.normal(ks[0], (2, 1, h, d))
    lengths = jnp.array([s - 195, s], jnp.int32)
    o_k = ops.flash_decode(q1, kc.transpose(0, 2, 1, 3),
                           vc.transpose(0, 2, 1, 3), lengths, slopes=slopes,
                           impl="pallas_interpret", block_k=128)
    o_r = ref.decode_reference(q1, kc, vc, lengths, slopes=slopes)
    rows.append(Row("decode_kernel_parity", 0.0,
                    f"max_err={float(jnp.abs(o_k - o_r).max()):.2e}"))

    # HEADLINE: dense-bias vs factored-bias cost of the same workload,
    # swept over seq lengths; the headline is the largest (paper-scale)
    # point — smoke keeps the historical small size in the sweep so the
    # small-N crossover stays visible, but never as the headline
    seqs = (128, 512) if smoke else (512, 1024, 2048)
    rank = 8 if smoke else 16
    sweep = [_dense_vs_factored(n=ni, rank=rank) for ni in seqs]
    ab = headline_point(sweep)
    for pt in sweep:
        rows.append(Row(f"attn_dense_bias_n{pt['seq_len']}",
                        pt["dense_bias_us"],
                        f"materialized (H,{pt['seq_len']},{pt['seq_len']}) "
                        "bias"))
        rows.append(Row(f"attn_factored_bias_n{pt['seq_len']}",
                        pt["factored_bias_us"],
                        f"rank-{pt['rank']} factors, "
                        f"{pt['speedup']:.2f}x vs dense"))

    payload = {"dense_vs_factored": ab,
               "dense_vs_factored_sweep": sweep,
               "parity": {"fig5_pallas_max_err": err,
                          "decode_kernel_max_err":
                          float(jnp.abs(o_k - o_r).max())}}
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for CI")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args(argv)
    rows = run(out_path=args.out, smoke=args.smoke)
    from benchmarks.common import print_rows
    print_rows(rows)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
