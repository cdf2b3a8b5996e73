"""Fused optimizer-update nd ops (reference src/operator/optimizer_op.cc:317)
+ round-4 registry stragglers (bipartite_matching, KL sparse reg, gelqf/syevd,
SparseEmbedding) + the legacy FeedForward estimator (model.py:452)."""

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, optimizer


def _rand(shape, seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


def test_sgd_update_matches_optimizer_class():
    wv, gv = _rand((5, 4), 1), _rand((5, 4), 2)
    # op path
    w = nd.array(wv)
    nd.sgd_update(w, nd.array(gv), out=w, lr=0.1, wd=0.01, rescale_grad=0.5)
    # optimizer path
    opt = optimizer.SGD(learning_rate=0.1, wd=0.01, rescale_grad=0.5)
    w2 = nd.array(wv)
    opt.update(0, w2, nd.array(gv), opt.create_state(0, w2))
    np.testing.assert_allclose(w.asnumpy(), w2.asnumpy(), rtol=1e-6, atol=1e-7)


def test_sgd_mom_update_matches_optimizer_class():
    wv, gv = _rand((6,), 3), _rand((6,), 4)
    w, mom = nd.array(wv), nd.zeros((6,))
    opt = optimizer.SGD(learning_rate=0.2, momentum=0.9, wd=0.001)
    w2 = nd.array(wv)
    state = opt.create_state(0, w2)
    for step in range(3):
        g = nd.array(gv * (step + 1))
        nd.sgd_mom_update(w, g, mom, lr=0.2, momentum=0.9, wd=0.001)
        state = opt.update(0, w2, g, state)
    np.testing.assert_allclose(w.asnumpy(), w2.asnumpy(), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(mom.asnumpy(), np.asarray(state[0]), rtol=1e-5,
                               atol=1e-6)


def test_adam_update_matches_optimizer_class():
    """The fused op omits bias correction (reference kernel contract) — the
    caller folds sqrt(1-b2^t)/(1-b1^t) into lr, as python optimizer.Adam does."""
    wv, gv = _rand((4, 3), 5), _rand((4, 3), 6)
    w = nd.array(wv)
    mean, var = nd.zeros((4, 3)), nd.zeros((4, 3))
    opt = optimizer.Adam(learning_rate=0.01, beta1=0.9, beta2=0.999,
                         epsilon=1e-8)
    w2 = nd.array(wv)
    state = opt.create_state(0, w2)
    for t in range(1, 4):
        g = nd.array(gv * t)
        coef = 0.01 * np.sqrt(1 - 0.999 ** t) / (1 - 0.9 ** t)
        nd.adam_update(w, g, mean, var, lr=float(coef), beta1=0.9,
                       beta2=0.999, epsilon=1e-8)
        state = opt.update(0, w2, g, state)
    np.testing.assert_allclose(w.asnumpy(), w2.asnumpy(), rtol=1e-5, atol=1e-6)


def test_fused_out_and_inplace_state_contract():
    """States mutate in place; weight goes to out= (reference FMutateInputs +
    out= convention) — without out=, weight itself is updated."""
    w, g = nd.array(_rand((3,), 7)), nd.array(_rand((3,), 8))
    mom = nd.zeros((3,))
    mom_id = id(mom)
    before = w.asnumpy().copy()
    ret = nd.sgd_mom_update(w, g, mom, lr=0.1, momentum=0.9)
    assert ret is w                        # default: weight updated in place
    assert id(mom) == mom_id and float(nd.sum(nd.abs(mom)).asscalar()) > 0
    assert not np.allclose(w.asnumpy(), before)

    dest = nd.zeros((3,))
    w2 = nd.array(before)
    ret2 = nd.sgd_update(w2, g, out=dest, lr=0.1)
    assert ret2 is dest
    np.testing.assert_allclose(w2.asnumpy(), before)   # untouched


def test_lazy_rowsparse_sgd_touches_only_live_rows():
    from mxtpu.ndarray import sparse
    wv = np.ones((6, 2), np.float32)
    w = nd.array(wv)
    grad = sparse.row_sparse_array((np.ones((2, 2), np.float32), [1, 4]),
                                   shape=(6, 2))
    nd.sgd_update(w, grad, lr=0.5, lazy_update=True)
    out = w.asnumpy()
    np.testing.assert_allclose(out[[0, 2, 3, 5]], 1.0)    # untouched rows
    np.testing.assert_allclose(out[[1, 4]], 0.5)          # 1 - 0.5*1


def test_lazy_rowsparse_adam_state_rows():
    from mxtpu.ndarray import sparse
    w = nd.array(np.ones((5, 3), np.float32))
    mean, var = nd.zeros((5, 3)), nd.zeros((5, 3))
    grad = sparse.row_sparse_array((np.full((1, 3), 2.0, np.float32), [2]),
                                   shape=(5, 3))
    nd.adam_update(w, grad, mean, var, lr=0.1, lazy_update=True)
    assert np.all(mean.asnumpy()[[0, 1, 3, 4]] == 0)
    assert np.all(mean.asnumpy()[2] != 0)
    assert np.all(w.asnumpy()[[0, 1, 3, 4]] == 1.0)


def test_mp_sgd_keeps_fp32_master():
    w16 = nd.array(_rand((8,), 9)).astype("float16")
    w32 = nd.array(w16.asnumpy().astype(np.float32))
    mom = nd.zeros((8,))
    g = nd.array(_rand((8,), 10)).astype("float16")
    nd.mp_sgd_mom_update(w16, g, mom, w32, lr=0.1, momentum=0.9)
    assert w16.dtype == np.float16 and w32.dtype == np.float32
    np.testing.assert_allclose(w16.asnumpy(),
                               w32.asnumpy().astype(np.float16))


@pytest.mark.parametrize("name,nstates,kw", [
    ("signsgd_update", 0, {"lr": 0.1, "wd": 0.01}),
    ("signum_update", 1, {"lr": 0.1, "momentum": 0.9, "wd_lh": 0.01}),
    ("rmsprop_update", 1, {"lr": 0.01, "gamma1": 0.95}),
    ("rmspropalex_update", 3, {"lr": 0.01, "gamma1": 0.95, "gamma2": 0.9}),
    ("ftrl_update", 2, {"lr": 0.1, "lamda1": 0.01, "beta": 1.0}),
    ("ftml_update", 3, {"lr": 0.01, "t": 1, "beta1": 0.6, "beta2": 0.999}),
])
def test_fused_family_runs_and_descends(name, nstates, kw):
    """Each fused op runs, mutates its states, and (on a quadratic bowl)
    steps the weight toward the minimum."""
    wv = np.full((16,), 3.0, np.float32)
    w = nd.array(wv)
    states = [nd.zeros((16,)) for _ in range(nstates)]
    fn = getattr(nd, name)
    for _ in range(5):
        g = nd.array(2.0 * w.asnumpy())          # d/dw of (w^2)
        fn(w, g, *states, **kw)
    assert np.all(np.abs(w.asnumpy()) < np.abs(wv)), w.asnumpy()[:4]
    assert np.all(np.isfinite(w.asnumpy()))


def test_signsgd_reference_formula():
    wv, gv = _rand((4,), 11), _rand((4,), 12)
    w = nd.array(wv)
    nd.signsgd_update(w, nd.array(gv), lr=0.1, wd=0.02)
    want = (1 - 0.1 * 0.02) * wv - 0.1 * np.sign(gv)
    np.testing.assert_allclose(w.asnumpy(), want, rtol=1e-6)


# ---------------------------------------------------------------------------
# stragglers
# ---------------------------------------------------------------------------


def test_bipartite_matching_reference_example():
    s = nd.array(np.array([[0.5, 0.6], [0.1, 0.2], [0.3, 0.4]], np.float32))
    x, y = nd.contrib.bipartite_matching(s, threshold=1e-12, is_ascend=False)
    np.testing.assert_array_equal(x.asnumpy(), [1, -1, 0])
    np.testing.assert_array_equal(y.asnumpy(), [2, 0])
    # batched + threshold stop
    b = nd.array(np.stack([s.asnumpy(), s.asnumpy() * 0.0 + 1e-15]))
    xb, yb = nd.contrib.bipartite_matching(b, threshold=1e-12)
    np.testing.assert_array_equal(xb.asnumpy()[0], [1, -1, 0])
    np.testing.assert_array_equal(xb.asnumpy()[1], [-1, -1, -1])


def test_identity_attach_kl_sparse_reg():
    from mxtpu import autograd
    x = nd.array(np.full((4, 3), 0.2, np.float32))
    x.attach_grad()
    with autograd.record():
        y = nd.IdentityAttachKLSparseReg(x, sparseness_target=0.1,
                                         penalty=0.01)
        loss = nd.sum(y) * 0.0
    loss.backward()
    np.testing.assert_allclose(y.asnumpy(), x.asnumpy())   # identity forward
    # rho_hat=0.2: grad = penalty * (-t/rho + (1-t)/(1-rho)) = 0.01*0.625
    np.testing.assert_allclose(x.grad.asnumpy(), 0.00625, rtol=1e-5)


def test_gelqf_syevd_reference_conventions():
    A = nd.array(np.array([[1., 2., 3.], [4., 5., 6.]], np.float32))
    q, l = nd.linalg_gelqf(A)
    np.testing.assert_allclose(l.asnumpy() @ q.asnumpy(), A.asnumpy(),
                               atol=1e-5)                  # A = L Q
    np.testing.assert_allclose(q.asnumpy() @ q.asnumpy().T, np.eye(2),
                               atol=1e-5)                  # Q row-orthonormal
    assert abs(l.asnumpy()[0, 1]) < 1e-6                   # L lower-triangular

    S = nd.array(np.array([[2., 1.], [1., 3.]], np.float32))
    u, lam = nd.linalg_syevd(S)
    np.testing.assert_allclose(
        u.asnumpy().T @ np.diag(lam.asnumpy()) @ u.asnumpy(), S.asnumpy(),
        atol=1e-5)                                         # A = Uᵀ diag(L) U


def test_sparse_embedding_alias():
    w = nd.array(np.arange(10, dtype=np.float32).reshape(5, 2))
    i = nd.array(np.array([1, 3], np.float32))
    out = nd.contrib.SparseEmbedding(i, w, input_dim=5, output_dim=2)
    np.testing.assert_allclose(out.asnumpy(), [[2, 3], [6, 7]])
    out2 = nd.SparseEmbedding(i, w, input_dim=5, output_dim=2)
    np.testing.assert_allclose(out2.asnumpy(), out.asnumpy())


# ---------------------------------------------------------------------------
# FeedForward estimator
# ---------------------------------------------------------------------------


def test_feedforward_fit_predict_save_load(tmp_path):
    from mxtpu import symbol as sym
    from mxtpu.model import FeedForward
    from mxtpu.symbol.symbol import _reset_names
    _reset_names()
    mx.rng.seed(0)   # init draws from the global RNG: make order-independent
    np.random.seed(0)   # and the fit from numpy's: 3 of 12 states end under 0.8

    rng = np.random.RandomState(0)
    X = rng.rand(64, 8).astype(np.float32)
    yv = (X.sum(axis=1) > 4.0).astype(np.float32)

    data = sym.Variable("data")
    net = sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="relu", name="relu1")
    net = sym.FullyConnected(net, num_hidden=2, name="fc2")
    net = sym.SoftmaxOutput(net, name="softmax")

    with pytest.warns(DeprecationWarning):
        model = FeedForward(net, num_epoch=30, optimizer="sgd",
                            numpy_batch_size=16, learning_rate=0.5)
    model.fit(X, yv)
    acc = model.score(mx.io.NDArrayIter(X, yv, 16))
    assert acc > 0.8, acc

    preds = model.predict(X)
    assert preds.shape[0] == 64 and preds.shape[1] == 2

    prefix = str(tmp_path / "ffn")
    model.save(prefix, 30)
    with pytest.warns(DeprecationWarning):
        loaded = FeedForward.load(prefix, 30)
    acc2 = loaded.score(mx.io.NDArrayIter(X, yv, 16))
    assert abs(acc2 - acc) < 1e-6, (acc, acc2)


def test_bipartite_matching_topk_strict():
    s = nd.array(np.array([[0.9, 0.8], [0.7, 0.6]], np.float32))
    x, _ = nd.contrib.bipartite_matching(s, threshold=1e-12, topk=1)
    assert int((x.asnumpy() >= 0).sum()) == 1, x.asnumpy()


def test_lazy_update_duplicate_rows_accumulate():
    # advisor r4: duplicate row ids must sum, not last-write-win
    w = nd.zeros((6, 3))
    rows = np.array([2, 4, 2], np.int64)
    vals = np.ones((3, 3), np.float32)
    g = mx.nd.sparse.row_sparse_array((vals, rows), shape=(6, 3))
    nd.sgd_update(w, g, lr=1.0, wd=0.0)
    out = w.asnumpy()
    np.testing.assert_allclose(out[2], -2.0)       # merged: two grads summed
    np.testing.assert_allclose(out[4], -1.0)
    np.testing.assert_allclose(out[[0, 1, 3, 5]], 0.0)


def test_ftrl_accepts_lazy_update_kwarg():
    w = nd.array(_rand((4,), 20))
    z, n = nd.zeros((4,)), nd.zeros((4,))
    nd.ftrl_update(w, nd.array(_rand((4,), 21)), z, n, lr=0.1,
                   lazy_update=False)          # wrapper kwarg, not kernel's
    assert np.all(np.isfinite(w.asnumpy()))
