#!/usr/bin/env python
"""MFU probe: ResNet-50 train-step analysis on the real chip (round-4
verdict #1). For each batch size it

1. AOT-compiles the DataParallelTrainer step and records XLA's own
   cost_analysis (flops, bytes accessed) and memory_analysis (peak HBM,
   temp/argument/output allocation) — the capacity story behind the
   batch-scaling curve;
2. dumps the optimized HLO to ``benchmark/hlo/`` for offline inspection
   (conv configs, fusion counts, remat);
3. runs a pipelined timed segment (synced by reading the last loss back)
   and reports img/s + MFU.

Usage: python benchmark/python/mfu_probe.py [--batches 128,256,512]
                                            [--steps 50] [--no-run]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

HLO_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "hlo")


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def probe(batch: int, dtype: str, steps: int, run: bool, peak_tf: float):
    import jax
    import jax.numpy as jnp

    from mxtpu import nd, optimizer as opt_mod
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxtpu.gluon.model_zoo import vision
    from mxtpu.parallel import DataParallelTrainer, shard_batch
    from mxtpu.parallel.mesh import data_parallel_mesh

    net = vision.resnet50_v1(classes=1000)
    net.initialize()
    if dtype != "float32":
        net.cast(dtype)
    mesh = data_parallel_mesh()
    dpt = DataParallelTrainer(
        net, SoftmaxCrossEntropyLoss(),
        opt_mod.SGD(learning_rate=0.05, momentum=0.9, wd=1e-4), mesh)

    rs = np.random.RandomState(0)
    x = shard_batch(nd.array(rs.rand(batch, 3, 224, 224).astype(dtype)), mesh)
    y = shard_batch(nd.array(rs.randint(0, 1000, batch).astype(np.int32)), mesh)

    t0 = time.perf_counter()
    loss = dpt.step_async(x, y)           # builds + compiles
    float(loss.data)
    compile_s = time.perf_counter() - t0

    compiled = dpt.lowered().compile()
    ca = dict(compiled.cost_analysis() or {})
    try:
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")
               if hasattr(ma, k)}
        if hasattr(ma, "peak_memory_in_bytes"):
            mem["peak_memory_in_bytes"] = int(ma.peak_memory_in_bytes)
    except Exception as e:                 # noqa: BLE001 — analysis optional
        mem = {"error": repr(e)}

    os.makedirs(HLO_DIR, exist_ok=True)
    hlo_path = os.path.join(HLO_DIR, f"resnet50_{dtype}_b{batch}.hlo.txt")
    try:
        with open(hlo_path, "w") as f:
            f.write(compiled.as_text())
    except Exception as e:                 # noqa: BLE001
        hlo_path = f"unavailable: {e!r}"

    out = {"batch": batch, "dtype": dtype, "compile_s": round(compile_s, 1),
           "xla_gflops": round(float(ca.get("flops", 0)) / 1e9, 1),
           "xla_gbytes": round(float(ca.get("bytes accessed", 0)) / 1e9, 3),
           "memory": mem, "hlo": os.path.basename(str(hlo_path))}

    if run:
        for _ in range(2):
            loss = dpt.step_async(x, y)
        float(loss.data)
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = dpt.step_async(x, y)
        float(loss.data)
        dt = time.perf_counter() - t0
        step_ms = 1e3 * dt / steps
        img_s = steps * batch / dt
        mfu = (float(ca.get("flops", 0)) / (step_ms / 1e3)) / (peak_tf * 1e12)
        out.update(step_ms=round(step_ms, 2), img_s=round(img_s, 1),
                   mfu=round(mfu, 4))
        # arithmetic intensity + roofline position
        bytes_step = float(ca.get("bytes accessed", 0))
        if bytes_step:
            out["arith_intensity"] = round(
                float(ca.get("flops", 0)) / bytes_step, 1)
            # v5e HBM ~819 GB/s
            out["hbm_bound_ms"] = round(1e3 * bytes_step / 819e9, 2)
    log(f"[probe b{batch}] {json.dumps(out)}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="128,256,512")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--no-run", action="store_true")
    args = ap.parse_args()

    from mxtpu import compile_cache
    from mxtpu.observability import flops
    compile_cache.place()
    kind, peak = flops.device_peak()    # raises on a TPU not in the table
    log(f"device: {kind} peak {peak} TF bf16")

    results = []
    for b in [int(v) for v in args.batches.split(",")]:
        results.append(probe(b, args.dtype, args.steps, not args.no_run, peak))
    print(json.dumps(results))


if __name__ == "__main__":
    main()
