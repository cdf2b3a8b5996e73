#!/usr/bin/env python
"""Control-flow RNN micro-benchmark — parity with the reference's
``benchmark/python/control_flow/`` foreach/while_loop RNN timing: unrolled
imperative cell loop vs the fused ``nd.contrib.foreach`` (lax.scan) path."""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--hidden", type=int, default=512)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--iters", type=int, default=5)
    args = p.parse_args()

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    import numpy as np
    import jax.numpy as jnp
    from mxtpu import gluon, nd

    rs = np.random.RandomState(0)
    cell = gluon.rnn.LSTMCell(args.hidden, input_size=args.hidden)
    cell.initialize()
    x = nd.array(rs.randn(args.seq_len, args.batch,
                          args.hidden).astype(np.float32))
    states = cell.begin_state(args.batch)

    def run_foreach():
        def step(inp, st):
            out, nst = cell(inp, st)
            return out, nst
        outs, _ = nd.contrib.foreach(step, x, states)
        return float(jnp.sum(outs.data[-1, 0, :1]))

    def run_unrolled():
        st = states
        out = None
        for t in range(args.seq_len):
            out, st = cell(x[t], st)
        return float(jnp.sum(out.data[0, :1]))

    for name, fn in (("foreach(scan)", run_foreach),
                     ("unrolled_eager", run_unrolled)):
        fn()  # warm/compile
        t0 = time.perf_counter()
        for _ in range(args.iters):
            fn()
        dt = (time.perf_counter() - t0) / args.iters
        steps_s = args.seq_len * args.batch / dt
        print(f"{name:>16}: {dt*1e3:8.1f} ms/seq  {steps_s:12.0f} cell-steps/s")


if __name__ == "__main__":
    main()
