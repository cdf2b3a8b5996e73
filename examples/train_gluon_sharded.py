#!/usr/bin/env python
"""Sharded SPMD training with the gluon + DataParallelTrainer path: one
compiled step over a dp x tp mesh (tensor-parallel Dense shardings), the
TPU-native equivalent of the reference's multi-GPU ``kv=device`` training.
Runs on however many devices are visible (use
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu to
simulate a pod on CPU)."""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch-size", type=int, default=64)
    args = p.parse_args()

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    import numpy as np
    import jax
    from jax.sharding import PartitionSpec as P

    import mxtpu as mx
    from mxtpu import gluon, nd, optimizer, parallel
    from mxtpu.gluon import nn

    n = len(jax.devices())
    tp = 2 if n % 2 == 0 and n >= 2 else 1
    dp = max(1, n // tp)
    mesh = parallel.make_mesh((dp, tp), ("dp", "tp"))
    print(f"devices={n} mesh=dp{dp} x tp{tp}")

    net = nn.HybridSequential()
    net.add(nn.Dense(256, activation="relu", in_units=64),
            nn.Dense(10, in_units=256))
    net.initialize(init=mx.initializer.Xavier())
    shardings = {"dense0_weight": P("tp", None), "dense0_bias": P("tp"),
                 "dense1_weight": P(None, "tp")}
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer.SGD(learning_rate=0.1, momentum=0.9), mesh,
        param_shardings=shardings)

    rs = np.random.RandomState(0)
    w_true = rs.randn(64, 10).astype(np.float32)
    for step in range(args.steps):
        x = rs.randn(args.batch_size, 64).astype(np.float32)
        y = np.argmax(x @ w_true, axis=1).astype(np.float32)
        loss = dpt.step(nd.array(x), nd.array(y))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {loss:.4f}")


if __name__ == "__main__":
    main()
