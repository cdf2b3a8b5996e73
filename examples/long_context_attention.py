#!/usr/bin/env python
"""Long-context attention demo — both sequence-parallel modes over one mesh.

The reference's longest-sequence tools were bucketing and fused RNNs; here a
single (B, H, T, D) attention call scales T across chips two ways:

* ring attention (``parallel.ring_attention``): K/V rotate around the ICI
  ring; per-device memory stays O(T/n) — the mode for sequences that don't
  fit even one head per device.
* all-to-all / Ulysses (``parallel.ulysses``): one collective reshuffles
  sequence-sharding into head-sharding, full attention runs per head group,
  one collective restores — two collectives total, the mode when heads >= n.

Both produce identical math; this demo runs a causal long-context pass with
each, checks they agree with the single-device oracle, and reports the
per-device memory footprint each mode holds.

Run on the virtual pod: JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/long_context_attention.py --seq-len 4096
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--batch", type=int, default=1)
    args = p.parse_args(argv)

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    import numpy as np

    import jax

    from mxtpu import nd, parallel
    from mxtpu.ops.attention import flash_chunk

    n = len(jax.devices())
    mesh = parallel.make_mesh((n,), ("sp",))
    B, H, T, D = args.batch, args.heads, args.seq_len, args.head_dim
    assert T % n == 0 and H % n == 0, "seq-len and heads must divide devices"

    rs = np.random.RandomState(0)
    q = rs.randn(B, H, T, D).astype(np.float32) * 0.5
    k = rs.randn(B, H, T, D).astype(np.float32) * 0.5
    v = rs.randn(B, H, T, D).astype(np.float32) * 0.5

    oracle = np.asarray(flash_chunk(q, k, v, True, 1.0 / D ** 0.5)[0])

    ring = parallel.ring_self_attention(nd.array(q), nd.array(k), nd.array(v),
                                        mesh=mesh, causal=True)
    uly = parallel.ulysses_self_attention(nd.array(q), nd.array(k),
                                          nd.array(v), mesh=mesh, causal=True)
    err_r = float(np.abs(ring.asnumpy() - oracle).max())
    err_u = float(np.abs(uly.asnumpy() - oracle).max())
    assert err_r < 2e-4 and err_u < 2e-4, (err_r, err_u)

    fp32 = 4
    per_dev_ring = 3 * B * H * (T // n) * D * fp32          # q,k,v chunks
    per_dev_uly = 3 * B * (H // n) * T * D * fp32           # full T, H/n heads
    print(f"devices={n} T={T} H={H} D={D}")
    print(f"ring:    max|err|={err_r:.2e}  resident qkv/device="
          f"{per_dev_ring / 1e6:.2f} MB (O(T/n))")
    print(f"ulysses: max|err|={err_u:.2e}  resident qkv/device="
          f"{per_dev_uly / 1e6:.2f} MB (full T, H/n heads)")
    print("LONG_CONTEXT_OK")


if __name__ == "__main__":
    main()
