#!/usr/bin/env python
"""Word-level language model — the reference's ``example/gluon/
word_language_model`` flow (Embedding → multi-layer LSTM → tied-weight
decoder, truncated BPTT with carried hidden state) on a synthetic corpus.

Zero-egress stand-in for WikiText: a deterministic order-2 Markov chain over
the vocabulary, so the data has real (and known) structure — an LM that learns
it reaches perplexity ≈ the chain's branching factor, far below the uniform
baseline of vocab_size. The training loop is the reference's: batchify to
(N_batch, T) streams, slide BPTT windows, detach state between windows,
clip gradients, decay LR.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_corpus(vocab: int, length: int, branch: int = 4, seed: int = 17):
    """First-order Markov chain: every token has ``branch`` fixed successors,
    drawn uniformly — per-token entropy log(branch), so a model that learns the
    transitions reaches perplexity ≈ branch."""
    import numpy as np
    rs = np.random.RandomState(seed)
    successors = rs.randint(vocab, size=(vocab, branch))
    data = np.empty(length, np.int64)
    data[0] = rs.randint(vocab)
    draws = rs.randint(branch, size=length)
    for t in range(1, length):
        data[t] = successors[data[t - 1], draws[t]]
    return data


def batchify(data, batch_size: int):
    """(len,) token stream → (batch, T) parallel streams (reference batchify)."""
    n = len(data) // batch_size
    return data[:n * batch_size].reshape(batch_size, n)


class RNNModel:
    """Embedding → LSTM → decoder (optionally tied to the embedding)."""

    def __init__(self, vocab, embed, hidden, layers, dropout, tied):
        from mxtpu import gluon
        from mxtpu.gluon import nn, rnn

        self.tied = tied
        net = nn.HybridSequential()
        self.embedding = nn.Embedding(vocab, embed)
        self.lstm = rnn.LSTM(hidden, num_layers=layers, dropout=dropout,
                             layout="TNC", input_size=embed)
        self.drop = nn.Dropout(dropout)
        if tied:
            if embed != hidden:
                raise ValueError("--tied requires embed == hidden")
            self.decoder = None  # reuse embedding weight
        else:
            self.decoder = nn.Dense(vocab, in_units=hidden, flatten=False)
        self.blocks = [b for b in (self.embedding, self.lstm, self.drop,
                                   self.decoder) if b is not None]

    def initialize(self, init):
        for b in self.blocks:
            b.initialize(init=init)

    def collect_params(self):
        params = {}
        for b in self.blocks:
            params.update(b.collect_params()._params)
        return params

    def __call__(self, x, states):
        """x: (T, N) int tokens → logits (T, N, vocab), new states."""
        from mxtpu import nd
        emb = self.drop(self.embedding(x))
        out, states = self.lstm(emb, states)
        out = self.drop(out)
        if self.tied:
            w = self.embedding.weight.data()       # (vocab, embed)
            logits = nd.dot(out, w, transpose_b=True)
        else:
            logits = self.decoder(out)
        return logits, states


def detach(states):
    return [s.detach() for s in states]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--vocab", type=int, default=200)
    p.add_argument("--corpus-len", type=int, default=40000)
    p.add_argument("--branch", type=int, default=4)
    p.add_argument("--embed", type=int, default=64)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--tied", action="store_true")
    p.add_argument("--batch-size", type=int, default=16)
    p.add_argument("--bptt", type=int, default=32)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--lr", type=float, default=2.0)
    p.add_argument("--clip", type=float, default=0.25)
    p.add_argument("--cpu", action="store_true", help="force the CPU backend")
    args = p.parse_args(argv)

    from mxtpu import compile_cache
    compile_cache.place()      # before the first jit

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import mxtpu as mx
    from mxtpu import autograd, gluon, nd

    mx.rng.seed(0)
    corpus = make_corpus(args.vocab, args.corpus_len, args.branch)
    split = int(0.9 * len(corpus))
    train_data = batchify(corpus[:split], args.batch_size)
    valid_data = batchify(corpus[split:], args.batch_size)

    model = RNNModel(args.vocab, args.embed, args.hidden, args.layers,
                     args.dropout, args.tied)
    model.initialize(mx.initializer.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    params = model.collect_params()
    trainer = gluon.Trainer(params, "sgd", {"learning_rate": args.lr})

    # one compiled BPTT window: hybridize-equivalent — the whole
    # embed→lstm→decode→loss graph runs as a single XLA program, with state
    # carried out (CachedOp re-traces once per train/predict mode)
    def window_loss(x, y, h, c):
        logits, (h2, c2) = model(x, [h, c])
        loss = loss_fn(logits.reshape((-1, args.vocab)), y.reshape((-1,)))
        return nd.mean(loss), h2, c2

    step = mx.jit.CachedOp(window_loss,
                           params=[p.data() for p in params.values()])

    def run_epoch(data, train: bool):
        total_loss, windows = 0.0, 0
        h, c = model.lstm.begin_state(args.batch_size)
        for start in range(0, data.shape[1] - 1 - args.bptt, args.bptt):
            x = nd.array(data[:, start:start + args.bptt].T.astype(np.int32))
            y = nd.array(
                data[:, start + 1:start + 1 + args.bptt].T.astype(np.int32))
            h, c = h.detach(), c.detach()
            if train:
                with autograd.record():
                    loss, h, c = step(x, y, h, c)
                loss.backward()
                gluon.utils.clip_global_norm(
                    [p.grad() for p in params.values()], args.clip)
                trainer.step(1)
            else:
                with autograd.predict_mode():
                    loss, h, c = step(x, y, h, c)
            total_loss += float(loss.asscalar())
            windows += 1
        return float(np.exp(total_loss / max(windows, 1)))

    uniform_ppl = args.vocab
    best = float("inf")
    for epoch in range(args.epochs):
        t0 = time.time()
        train_ppl = run_epoch(train_data, train=True)
        valid_ppl = run_epoch(valid_data, train=False)
        if valid_ppl >= best:          # reference: anneal LR when stuck
            trainer.set_learning_rate(trainer.learning_rate / 4.0)
        best = min(best, valid_ppl)
        print(f"epoch {epoch}: train_ppl={train_ppl:.2f} "
              f"valid_ppl={valid_ppl:.2f} (uniform={uniform_ppl}, "
              f"chain={args.branch}) lr={trainer.learning_rate:g} "
              f"[{time.time() - t0:.1f}s]")
    return best


if __name__ == "__main__":
    ppl = main()
    print(f"final valid perplexity: {ppl:.2f}")
