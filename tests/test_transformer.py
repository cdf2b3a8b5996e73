"""TransformerLM model-zoo family: shapes, causality, weight tying, autograd,
and end-to-end learning through DataParallelTrainer (the flagship training
workload's correctness gate — the perf side is ``benchmark/suite``)."""

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import autograd, nd
from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxtpu.gluon.model_zoo import transformer_lm
from mxtpu.gluon.model_zoo.transformer import TransformerLM

VOCAB = 50


def _tiny(**kw):
    mx.rng.seed(0)
    net = transformer_lm("tiny", vocab_size=VOCAB, **kw)
    net.initialize()
    return net


def test_forward_shape_and_max_len():
    net = _tiny()
    x = nd.array(np.random.RandomState(0).randint(0, VOCAB, (2, 16)), dtype="int32")
    with autograd.predict_mode():
        out = net(x)
    assert out.shape == (2, 16, VOCAB)
    too_long = nd.array(np.zeros((1, 512), np.int32))
    with pytest.raises(ValueError, match="max_len"):
        with autograd.predict_mode():
            net(too_long)


def test_causality():
    """Changing token t must not change logits at positions < t."""
    net = _tiny()
    rs = np.random.RandomState(1)
    toks = rs.randint(0, VOCAB, (1, 16)).astype(np.int32)
    with autograd.predict_mode():
        base = net(nd.array(toks)).asnumpy()
    toks2 = toks.copy()
    toks2[0, 10] = (toks2[0, 10] + 7) % VOCAB
    with autograd.predict_mode():
        pert = net(nd.array(toks2)).asnumpy()
    np.testing.assert_allclose(base[0, :10], pert[0, :10], rtol=1e-4, atol=1e-5)
    assert np.abs(base[0, 10:] - pert[0, 10:]).max() > 1e-4


def test_tied_head_shares_embedding():
    def n_vocab_mats(net):
        return sum(1 for p in net.collect_params().values()
                   if len(p.shape or ()) == 2 and VOCAB in tuple(p.shape))

    net = _tiny()
    assert n_vocab_mats(net) == 1                       # embedding only
    untied = transformer_lm("tiny", vocab_size=VOCAB, tie_weights=False)
    untied.initialize()
    assert n_vocab_mats(untied) == 2                    # + separate head

    # perturbing the embedding table changes the logits (the head reads it)
    x = nd.array(np.arange(8, dtype=np.int32).reshape(1, 8))
    with autograd.predict_mode():
        a = net(x).asnumpy()
    w = net.embedding.weight
    w.set_data(w.data() * 2.0)
    with autograd.predict_mode():
        b = net(x).asnumpy()
    assert np.abs(a - b).max() > 1e-3


def test_eager_autograd_reaches_all_params():
    """The imperative tape path: loss.backward() must deposit grads on the
    embedding (shared by lookup AND tied head), pos table, and block params."""
    net = _tiny()
    x = nd.array(np.random.RandomState(2).randint(0, VOCAB, (2, 8)), dtype="int32")
    y = nd.array(np.random.RandomState(3).randint(0, VOCAB, (2 * 8,)).astype(np.float32))
    loss_fn = SoftmaxCrossEntropyLoss()
    with autograd.predict_mode():
        net(x)                      # materialize deferred params (attaches grads)
    params = net.collect_params()
    with autograd.record():
        logits = net(x)
        loss = nd.mean(loss_fn(logits.reshape((16, VOCAB)), y))
    loss.backward()
    for name, p in params.items():
        if p.grad_req == "null":
            continue
        g = p.grad()
        assert float(nd.sum(nd.abs(g)).asscalar()) > 0, f"zero grad: {name}"


def test_learns_through_data_parallel_trainer():
    """Memorize one batch on the 8-device CPU mesh: loss must fall well below
    the uniform floor ln(V) and keep decreasing."""
    from mxtpu import optimizer
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh

    net = _tiny()
    mesh = data_parallel_mesh()
    dpt = DataParallelTrainer(
        net, _SeqLoss(), optimizer.Adam(learning_rate=3e-3), mesh,
        micro_batches=2)
    rs = np.random.RandomState(0)
    x = nd.array(rs.randint(0, VOCAB, (8, 16)), dtype="int32")
    y = nd.array(rs.randint(0, VOCAB, (8, 16)).astype(np.float32))
    first = dpt.step(x, y)
    losses = [dpt.step(x, y) for _ in range(40)]
    assert first > 0.5 * np.log(VOCAB), first          # starts near uniform
    assert losses[-1] < first - 0.5, (first, losses[-1])
    assert losses[-1] < losses[4], losses


class _SeqLoss:
    def __call__(self, logits, y):
        B, T, V = logits.shape
        return SoftmaxCrossEntropyLoss()(
            logits.reshape((B * T, V)), y.reshape((B * T,)))


def test_flagship_preset_constructs():
    """The bench config must build without materializing full-size params
    (constructor only — no initialize)."""
    net = transformer_lm("flagship")
    assert net._units == 1024 and len(net.blocks) == 8


def test_generate_matches_full_forward_greedy():
    """The KV-cache decode program must agree with the full forward: at every
    generated position, the emitted token equals the argmax of a fresh
    full-sequence forward over the tokens so far."""
    net = _tiny()
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, VOCAB, (2, 6)).astype(np.int32)
    out = net.generate(nd.array(prompt), max_new_tokens=5).asnumpy()
    assert out.shape == (2, 11)
    np.testing.assert_array_equal(out[:, :6], prompt)
    seq = prompt.copy()
    for t in range(5):
        with autograd.predict_mode():
            logits = net(nd.array(seq)).asnumpy()
        nxt = logits[:, -1].argmax(axis=-1).astype(np.int32)
        np.testing.assert_array_equal(out[:, 6 + t], nxt,
                                      err_msg=f"step {t}")
        seq = np.concatenate([seq, nxt[:, None]], axis=1)


def test_generate_sampling_and_limits():
    net = _tiny()
    rs = np.random.RandomState(6)
    prompt = nd.array(rs.randint(0, VOCAB, (1, 4)), dtype="int32")
    a = net.generate(prompt, 6, greedy=False, seed=1).asnumpy()
    b = net.generate(prompt, 6, greedy=False, seed=1).asnumpy()
    c = net.generate(prompt, 6, greedy=False, seed=2).asnumpy()
    np.testing.assert_array_equal(a, b)          # seeded: deterministic
    assert a.shape == (1, 10) and c.shape == (1, 10)
    with pytest.raises(ValueError, match="max_len"):
        net.generate(prompt, 10_000)
    with pytest.raises(ValueError, match="non-empty"):
        net.generate(nd.array(np.zeros((1, 0), np.int32)), 4)


def test_generate_untied_head_and_bucket_reuse():
    """tie_weights=False must decode through the separate head, and prompts
    within one 32-bucket must share a compiled program."""
    mx.rng.seed(1)
    net = transformer_lm("tiny", vocab_size=VOCAB, tie_weights=False)
    net.initialize()
    rs = np.random.RandomState(7)
    p1 = rs.randint(0, VOCAB, (1, 5)).astype(np.int32)
    out = net.generate(nd.array(p1), 4).asnumpy()
    # consistency vs full forward (exercises the head path)
    seq = p1.copy()
    for t in range(4):
        with autograd.predict_mode():
            logits = net(nd.array(seq)).asnumpy()
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        np.testing.assert_array_equal(out[:, 5 + t], nxt, err_msg=f"step {t}")
        seq = np.concatenate([seq, nxt[:, None]], 1)
    # a second prompt of different length in the same bucket: no new program
    n_prog = len(net._gen_fns)
    net.generate(nd.array(rs.randint(0, VOCAB, (1, 9)).astype(np.int32)), 4)
    assert len(net._gen_fns) == n_prog


def test_quantize_net_composes_with_transformer():
    """int8 LM serving: quantize_net swaps the projection/FFN Dense layers
    for int8 twins and the quantized model's next-token choices agree."""
    from mxtpu.contrib import quantization as q
    net = _tiny()
    x = nd.array(np.random.RandomState(8).randint(0, VOCAB, (2, 16)),
                 dtype="int32")
    with autograd.predict_mode():
        want = net(x).asnumpy()
    qnet = q.quantize_net(net, calib_data=[x], calib_mode="naive")
    with autograd.predict_mode():
        got = qnet(x).asnumpy()
    agree = (got.argmax(-1) == want.argmax(-1)).mean()
    assert agree > 0.9, f"int8 transformer top-1 agreement {agree}"
