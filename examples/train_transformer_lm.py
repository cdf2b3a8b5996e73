#!/usr/bin/env python
"""Decoder-transformer language model — the flagship training workload
(``gluon.model_zoo.transformer_lm``: pre-LN blocks over the Pallas flash
attention kernel, tied softmax head) trained through ``DataParallelTrainer``.

Zero-egress stand-in for a text corpus: the same planted first-order Markov
chain as ``train_word_lm.py`` — per-token entropy log(branch), so a model
that learns the transitions reaches perplexity ≈ branch, far below the
uniform baseline of vocab_size. One fwd+bwd+Adam step is ONE compiled SPMD
program; sequences are non-overlapping windows of the token stream.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from examples.train_word_lm import make_corpus  # noqa: E402  (same corpus)


def main(argv=None) -> float:
    import numpy as np

    import mxtpu as mx
    from mxtpu import nd, optimizer
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxtpu.gluon.model_zoo import transformer_lm
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--vocab", type=int, default=60)
    p.add_argument("--corpus-len", type=int, default=20000)
    p.add_argument("--seq-len", type=int, default=32)
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--units", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=2)
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--micro-batches", type=int, default=1)
    args = p.parse_args(argv)

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    mx.rng.seed(0)
    data = make_corpus(args.vocab, args.corpus_len)
    T = args.seq_len
    n_seq = (len(data) - 1) // T
    x_all = data[:n_seq * T].reshape(n_seq, T).astype(np.int32)
    y_all = data[1:n_seq * T + 1].reshape(n_seq, T).astype(np.float32)
    n_val = max(1, n_seq // 10)
    x_tr, y_tr = x_all[:-n_val], y_all[:-n_val]
    x_va, y_va = x_all[-n_val:], y_all[-n_val:]

    net = transformer_lm("tiny", vocab_size=args.vocab, units=args.units,
                         num_layers=args.layers, num_heads=args.heads,
                         max_len=max(256, T))
    net.initialize()

    class SeqLoss:
        def __call__(self, logits, y):
            b, t, v = logits.shape
            return SoftmaxCrossEntropyLoss()(
                logits.reshape((b * t, v)), y.reshape((b * t,)))

    dpt = DataParallelTrainer(net, SeqLoss(),
                              optimizer.Adam(learning_rate=args.lr),
                              data_parallel_mesh(),
                              micro_batches=args.micro_batches)

    B = args.batch_size
    n_batches = len(x_tr) // B
    for epoch in range(args.epochs):
        tic = time.time()
        perm = np.random.RandomState(epoch).permutation(len(x_tr))
        total = 0.0
        for i in range(n_batches):
            idx = perm[i * B:(i + 1) * B]
            total += dpt.step(nd.array(x_tr[idx]), nd.array(y_tr[idx]))
        print(f"epoch {epoch}: train loss {total / n_batches:.3f} "
              f"({time.time() - tic:.1f}s)")

    # validation perplexity, batched through the same block
    from mxtpu import autograd
    losses = []
    loss_fn = SeqLoss()
    for i in range(0, len(x_va), B):
        xb, yb = x_va[i:i + B], y_va[i:i + B]
        with autograd.predict_mode():
            logits = net(nd.array(xb))
            losses.append(float(
                nd.mean(loss_fn(logits, nd.array(yb))).asscalar())
                * len(xb))
    val_loss = sum(losses) / len(x_va)
    ppl = float(np.exp(val_loss))
    print(f"valid ppl {ppl:.2f} (uniform baseline {args.vocab})")
    return ppl


if __name__ == "__main__":
    main()
