#!/usr/bin/env python
"""Post-training INT8 quantization demo — train a small net fp32, quantize
with entropy calibration, compare accuracy and agreement (the reference's
``example/quantization`` flow re-based on gluon + the int8 MXU path)."""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--calib-mode", default="entropy",
                   choices=["none", "naive", "entropy"])
    p.add_argument("--quantized-dtype", default="auto",
                   choices=["int8", "uint8", "auto"])
    args = p.parse_args()

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    import numpy as np

    from mxtpu import autograd, gluon, nd
    from mxtpu.contrib import quantization as qz
    from mxtpu.gluon import nn

    rs = np.random.RandomState(0)
    x = rs.randn(512, 32).astype(np.float32)
    w_true = rs.randn(32, 4).astype(np.float32)
    y = np.argmax(x @ w_true, axis=1)

    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(4))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    xa, ya = nd.array(x), nd.array(y.astype(np.float32))
    for _ in range(80):
        with autograd.record():
            L = lossfn(net(xa), ya).mean()
        L.backward()
        trainer.step(1)

    with autograd.predict_mode():
        fp32_pred = np.argmax(net(xa).asnumpy(), axis=1)
    calib = [nd.array(x[i * 128:(i + 1) * 128]) for i in range(4)]
    qnet = qz.quantize_net(net, quantized_dtype=args.quantized_dtype,
                           calib_mode=args.calib_mode,
                           calib_data=calib if args.calib_mode != "none"
                           else None)
    with autograd.predict_mode():
        q_pred = np.argmax(qnet(xa).asnumpy(), axis=1)
    print(f"fp32 acc:  {(fp32_pred == y).mean():.4f}")
    print(f"quant acc: {(q_pred == y).mean():.4f}  "
          f"(calib={args.calib_mode}, dtype={args.quantized_dtype})")
    print(f"agreement: {(q_pred == fp32_pred).mean():.4f}")


if __name__ == "__main__":
    main()
