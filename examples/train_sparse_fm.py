#!/usr/bin/env python
"""Factorization machine on LibSVM data with dist_sync KVStore — the
reference's ``example/sparse/factorization_machine`` flow (BASELINE config 5).

The full sparse pipeline composes end-to-end:

  LibSVM file → ``LibSVMIter`` CSR batches → sparse forward
  (``sparse.dot(csr, dense)``) → **row-sparse gradients** via the transposed
  sparse dot (the DotCsrTransDnsRsp rule the reference registers for its
  sparse linear ops) → ``kvstore dist_sync`` sparse push + ``row_sparse_pull``
  → lazy SGD that touches only the rows present in the batch.

FM model (Rendle 2010): s(x) = w0 + x·w + ½ Σ_f [(x·V)_f² − (x²·V²)_f],
logistic loss. Gradients are the classic closed forms — expressed with the
framework's sparse ops so every grad is row-sparse:
  ∂L/∂w = Xᵀδ,   ∂L/∂V = Xᵀ(δ ⊙ XV) − (X²)ᵀ(δ·1) ⊙ V-rows
with δ = σ(s) − y.

Synthetic task: planted sparse logistic model over a large vocabulary; only
O(nnz) rows of w/V are ever touched per step — the capability the reference's
row-sparse parameter-server protocol exists for.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def write_libsvm(path, rs, n_rows, n_feat, nnz, w_true):
    """Synthetic planted-model LibSVM file: label = 1[σ(x·w_true) > 0.5]."""
    import numpy as np
    with open(path, "w") as f:
        for _ in range(n_rows):
            idx = np.sort(rs.choice(n_feat, nnz, replace=False))
            val = rs.rand(nnz).astype(np.float32) + 0.5
            score = float((val * w_true[idx]).sum())
            label = 1 if score > 0 else 0
            cols = " ".join(f"{i}:{v:.4f}" for i, v in zip(idx, val))
            f.write(f"{label} {cols}\n")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-features", type=int, default=10000)
    p.add_argument("--rank", type=int, default=8)
    p.add_argument("--rows", type=int, default=2000)
    p.add_argument("--nnz", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--lr", type=float, default=0.5)
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args(argv)

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import tempfile

    import numpy as np

    import mxtpu as mx
    from mxtpu import kvstore, nd
    from mxtpu.io import LibSVMIter
    from mxtpu.ndarray import sparse

    mx.rng.seed(0)
    rs = np.random.RandomState(0)
    D, F = args.num_features, args.rank

    w_true = np.zeros(D, np.float32)
    active = rs.choice(D, D // 10, replace=False)
    w_true[active] = rs.randn(len(active)).astype(np.float32) * 2.0

    path = os.path.join(tempfile.mkdtemp(), "fm.libsvm")
    write_libsvm(path, rs, args.rows, D, args.nnz, w_true)

    # dist_sync semantics: named params live in the store; workers push
    # row-sparse grads and pull back only the rows they need
    kv = kvstore.create("dist_sync")
    w = nd.zeros((D, 1))
    V = nd.array(rs.randn(D, F).astype(np.float32) * 0.01)
    kv.init("w", w)
    kv.init("V", V)
    lr = args.lr

    def lazy_sgd(key, grad, stored):
        """Row-sparse updater: touch only pushed rows (lazy SGD parity)."""
        if getattr(grad, "stype", "default") == "row_sparse":
            rows = grad.indices.asnumpy().astype(int)
            dense = stored.data.at[rows].add(-lr * grad.data.data)
            stored._set_data(dense)
        else:
            stored._set_data(stored.data - lr * grad.data)

    kv._set_updater(lazy_sgd)

    def forward(X, w_rows, V_rows):
        """FM score + δ-ready pieces. X csr (B, D)."""
        xw = sparse.dot(X, w_rows)                     # (B, 1)
        xv = sparse.dot(X, V_rows)                     # (B, F)
        x2 = sparse.csr_matrix(
            (X.data.asnumpy() ** 2, X.indices.asnumpy(), X.indptr.asnumpy()),
            shape=X.shape)
        v2 = nd.array(np.asarray(V_rows.data) ** 2)
        x2v2 = sparse.dot(x2, v2)                      # (B, F)
        score = xw.data[:, 0] + 0.5 * (
            np.asarray(xv.data) ** 2 - np.asarray(x2v2.data)).sum(axis=1)
        return np.asarray(score), xv, x2

    hits = total = 0
    for epoch in range(args.epochs):
        it = LibSVMIter(data_libsvm=path, data_shape=(D,),
                        batch_size=args.batch_size)
        correct = seen = 0
        for batch in it:
            X = batch.data[0]                           # CSRNDArray
            y = batch.label[0].asnumpy().reshape(-1)
            n = X.shape[0] - batch.pad
            score, xv, x2 = forward(X, w, V)
            prob = 1.0 / (1.0 + np.exp(-score))
            correct += int(((prob > 0.5) == (y > 0.5))[:n].sum())
            seen += n
            delta = ((prob - y) / max(n, 1)).astype(np.float32)
            if batch.pad:
                delta[n:] = 0.0
            dnd = nd.array(delta[:, None])
            grad_w = sparse.dot(X, dnd, transpose_a=True)          # rsp (D,1)
            grad_v1 = sparse.dot(
                X, nd.array(delta[:, None] * np.asarray(xv.data)),
                transpose_a=True)                                  # rsp (D,F)
            g2 = sparse.dot(x2, dnd, transpose_a=True)             # rsp (D,1)
            rows = g2.indices.asnumpy().astype(int)
            grad_v = sparse.row_sparse_array(
                (np.asarray(grad_v1.data.data)
                 - np.asarray(g2.data.data) * np.asarray(V.data)[rows],
                 grad_v1.indices.asnumpy()), shape=(D, F))
            kv.push("w", grad_w)
            kv.push("V", grad_v)
            # true sparse pull: only the touched rows come back
            w_rows = sparse.row_sparse_array(
                (np.zeros((len(rows), 1), np.float32), rows), shape=(D, 1))
            kv.row_sparse_pull("w", out=w_rows, row_ids=nd.array(rows))
            kv.pull("w", out=w)
            kv.pull("V", out=V)
        acc = correct / max(seen, 1)
        print(f"epoch {epoch}: train_acc={acc:.3f} "
              f"(rank {kv.rank}/{kv.num_workers})")
        hits, total = correct, seen
    return hits / max(total, 1)


if __name__ == "__main__":
    acc = main()
    print(f"final accuracy: {acc:.3f}")
