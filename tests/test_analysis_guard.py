"""Tier-1 analysis guards.

Two contracts future PRs cannot silently break:

1. **Self-lint clean** — ``python -m mxtpu.analysis mxtpu tests``
   exits 0 on the committed tree (the library AND its tests).  A new unlocked counter dict, a stray host sync in a traced
   step, or a swallowed producer error fails CI with the rule name and
   line, not a flaky hang three PRs later.  Findings a test legitimately
   stages (e.g. the observability off-path identity assert) carry an
   inline ``# mxtpu: ignore[Rnnn]`` with a justification comment.
2. **Sanitized fit is bit-exact and clean** — a 2-epoch LeNet ``Module.fit``
   under ``MXTPU_SANITIZE=transfers,donation,retrace,threads`` produces
   bit-identical parameters to the unsanitized run and reports zero
   violations: the sanitizers observe, they never perturb.
"""

import os
import subprocess
import sys

import numpy as np

import conftest
import mxtpu as mx
from mxtpu import nd, profiler
from mxtpu.analysis import sanitize
from mxtpu.gluon import nn
from mxtpu.gluon.block import HybridBlock
from mxtpu.io import NDArrayIter

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_self_lint_clean():
    """The committed tree — library and tests — passes its own
    linter (and the linter actually ran: a crash would exit 2/1 with
    output)."""
    p = subprocess.run(
        [sys.executable, "-m", "mxtpu.analysis", "mxtpu", "tests",
         "--stats"],
        cwd=_REPO, env=conftest.subprocess_env(),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, (
        f"tpulint found violations (rc={p.returncode}):\n"
        f"{p.stdout}\n{p.stderr[-1000:]}")


class _LeNet(HybridBlock):
    def __init__(self):
        super().__init__()
        self.c1 = nn.Conv2D(6, kernel_size=3, in_channels=1)
        self.p1 = nn.MaxPool2D(pool_size=2)
        self.flat = nn.Flatten()
        self.fc1 = nn.Dense(32, in_units=6 * 5 * 5)
        self.fc2 = nn.Dense(10, in_units=32)

    def forward(self, x):
        return self.fc2(self.fc1(self.flat(self.p1(self.c1(x).relu()))).relu())


def _fit_lenet(epochs=2, batch=16, n=64):
    rs = np.random.RandomState(42)
    x = rs.rand(n, 1, 12, 12).astype(np.float32)
    y = rs.randint(0, 10, n).astype(np.float32)
    it = NDArrayIter(x, y, batch_size=batch, shuffle=False)
    mx.rng.seed(0)
    np.random.seed(0)
    mod = mx.Module(_LeNet(), data_names=("data",),
                    label_names=("softmax_label",))
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    arg, aux = mod.get_params()
    # positional, not by name: block instance counters differ between
    # same-process instantiations (conv2d0_ vs conv2d1_); order is
    # construction order either way
    return [v.asnumpy() for v in list(arg.values()) + list(aux.values())]


def test_lenet_fit_sanitized_bit_exact_and_clean():
    plain = _fit_lenet()
    profiler.reset_sanitizer_stats()
    with sanitize.scope("transfers,donation,retrace,threads"):
        sanitized = _fit_lenet()
    stats = profiler.get_sanitizer_stats()
    # clean: the committed training path trips nothing...
    assert profiler.sanitizer_violations(stats) == 0, stats
    # ...while the detectors demonstrably ran
    assert stats["transfer_guards"] > 0
    assert stats["donation_poisons_armed"] > 0
    assert stats["ownership_checks"] > 0
    # bit-exact: sanitizers observe, they never perturb the computation
    assert len(plain) == len(sanitized)
    for i, (a, b) in enumerate(zip(plain, sanitized)):
        assert np.array_equal(a, b), (
            f"param #{i} diverged under MXTPU_SANITIZE")


def test_sanitize_env_var_is_the_knob():
    """MXTPU_SANITIZE is read by configure(): the env-var spelling of the
    knob map in docs/static_analysis.md."""
    old = os.environ.get("MXTPU_SANITIZE")
    os.environ["MXTPU_SANITIZE"] = "donation,retrace"
    try:
        modes = sanitize.configure()
        assert modes == frozenset({"donation", "retrace"})
    finally:
        if old is None:
            os.environ.pop("MXTPU_SANITIZE", None)
        else:
            os.environ["MXTPU_SANITIZE"] = old
        sanitize.configure("")
