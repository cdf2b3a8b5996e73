#!/usr/bin/env python
"""Sparse op micro-benchmarks — parity with the reference's
``benchmark/python/sparse/`` suite (sparse dot / elemwise / cast_storage
throughput over density sweeps)."""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--rows", type=int, default=65536)
    p.add_argument("--cols", type=int, default=512)
    p.add_argument("--densities", default="0.01,0.05,0.2")
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    import numpy as np
    import jax.numpy as jnp
    from mxtpu import nd
    from mxtpu.ndarray import sparse

    rs = np.random.RandomState(0)
    dense_w = nd.array(rs.randn(args.cols, args.cols).astype(np.float32))
    print(f"{'density':>8} {'op':>14} {'ms/iter':>10} {'GFLOP/s':>10}")
    for density in (float(d) for d in args.densities.split(",")):
        n_rows = max(1, int(args.rows * density))
        rows = np.sort(rs.choice(args.rows, n_rows, replace=False))
        vals = rs.randn(n_rows, args.cols).astype(np.float32)
        rsp = sparse.row_sparse_array((vals, rows),
                                      shape=(args.rows, args.cols))
        mask = rs.rand(args.rows, args.cols) < density
        csr = sparse.cast_storage(nd.array(
            (rs.randn(args.rows, args.cols) * mask).astype(np.float32)), "csr")
        nnz = csr.nnz
        # each op CHAINS through its accumulator so the final readback
        # transitively depends on every iteration (one sync for the chain)
        def run_dot(iters):
            w = dense_w
            for _ in range(iters):
                w = sparse.dot(csr, w) * (1.0 / args.cols)
            return float(jnp.sum(w.data[:1]))

        def run_add(iters):
            acc = rsp
            for _ in range(iters):
                acc = sparse.add(acc, rsp)
            return float(jnp.sum(acc.data.data[:1]))

        def run_cast(iters):
            acc = jnp.zeros((args.cols,), jnp.float32)
            cur = rsp
            for _ in range(iters):
                dense = cur._dense()
                acc = acc + dense[0]
                cur = sparse.row_sparse_array(
                    (cur.data.data + acc[0] * 0, cur.indices.data),
                    shape=cur.shape)
            return float(jnp.sum(acc[:1]))

        for name, fn, flops in (
            ("csr_dot_dense", run_dot, 2 * nnz * args.cols),
            ("rsp_add_rsp", run_add, n_rows * args.cols),
            ("cast_dense", run_cast, n_rows * args.cols),
        ):
            fn(1)  # warm/compile
            t0 = time.perf_counter()
            fn(args.iters)
            dt = (time.perf_counter() - t0) / args.iters
            print(f"{density:>8.2f} {name:>14} {dt*1e3:>10.2f} "
                  f"{flops/dt/1e9:>10.1f}")


if __name__ == "__main__":
    main()
