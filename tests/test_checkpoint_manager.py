"""Fault-tolerant async checkpoint subsystem (mxtpu/checkpoint/): atomic
commit protocol, crash-mid-save discovery, bit-exact restore (params +
optimizer slots + RNG), retention GC, legacy-layout compat, fit(resume_from),
and the satellite fixes (atomic nd.save, load_checkpoint warnings,
Speedometer divide-by-zero). CPU-only, tier-1."""

import json
import os
import signal
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import callback, nd, profiler
from mxtpu.checkpoint import CheckpointManager, atomic_io, strip_amp_cast
from mxtpu.gluon import nn
from mxtpu.gluon.block import HybridBlock
from mxtpu.io import DataBatch, DataDesc

from conftest import subprocess_env


class _Boom(Exception):
    pass


def _boom():
    raise _Boom()


# ---------------------------------------------------------------------------
# model fixtures
# ---------------------------------------------------------------------------


class LeNet(HybridBlock):
    def __init__(self):
        super().__init__()
        self.c1 = nn.Conv2D(4, kernel_size=3, in_channels=1)
        self.fc1 = nn.Dense(16, in_units=4 * 26 * 26)
        self.fc2 = nn.Dense(10, in_units=16)

    def forward(self, x):
        return self.fc2(self.fc1(self.c1(x).relu().reshape((0, -1))).relu())


def _lenet_module(seed=7, batch=8):
    mx.rng.seed(seed)
    mod = mx.Module(LeNet(), data_names=("data",),
                    label_names=("softmax_label",))
    mod.bind(data_shapes=[DataDesc("data", (batch, 1, 28, 28))],
             label_shapes=[DataDesc("softmax_label", (batch,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    return mod


def _batch(batch=8, seed=0):
    rs = np.random.RandomState(seed)
    return DataBatch(
        data=[nd.array(rs.rand(batch, 1, 28, 28).astype(np.float32))],
        label=[nd.array(rs.randint(0, 10, batch).astype(np.float32))])


def _params_np(mod):
    return {k: v.asnumpy().copy() for k, v in mod.get_params()[0].items()}


# ---------------------------------------------------------------------------
# tentpole: manager save/restore
# ---------------------------------------------------------------------------


def test_save_restore_bitexact_with_optimizer_and_rng(tmp_path):
    """The acceptance bar: restore from latest_step() reproduces the last
    committed params + optimizer slots + RNG bit-exactly, and continued
    training matches an uninterrupted run step-for-step."""
    b = _batch()
    mod = _lenet_module()
    for _ in range(3):
        mod.forward_backward(b)
        mod.update()
    mgr = CheckpointManager(tmp_path)
    mod.save_checkpoint(mgr, 3)           # manager mode: full state, blocking
    saved = _params_np(mod)
    rng_at_save = mx.rng.get_state_blob()

    for _ in range(2):                    # the uninterrupted continuation
        mod.forward_backward(b)
        mod.update()
    continued = _params_np(mod)

    mod2 = _lenet_module(seed=99)         # different init — restore must win
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # positional-match notice
        snap = mgr.restore(module=mod2)
    assert snap.step == 3
    for v1, v2 in zip(saved.values(), _params_np(mod2).values()):
        np.testing.assert_array_equal(v1, v2)
    np.testing.assert_array_equal(mx.rng.get_state_blob()["key_data"],
                                  rng_at_save["key_data"])
    for s1, s2 in zip(mod._trainer._states, mod2._trainer._states):
        assert (s1 is None) == (s2 is None)
    assert mod2._trainer._optimizer.num_update == 3

    for _ in range(2):                    # resumed continuation: bit-exact
        mod2.forward_backward(b)
        mod2.update()
    for v1, v2 in zip(continued.values(), _params_np(mod2).values()):
        np.testing.assert_array_equal(v1, v2)
    mgr.close()


def test_crash_mid_save_never_exposes_torn_checkpoint(tmp_path):
    """Kill the writer at every window of the commit protocol: before any
    file, before the dir rename, between rename and COMMIT marker. In all
    cases latest_step() stays at the previous committed step and restore
    reproduces it exactly."""
    arrs = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.ones(4, np.float32)}
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, arg_params=arrs, blocking=True)

    for hook in ("before_write", "before_rename", "before_marker"):
        mgr._test_hooks = {hook: _boom}
        with pytest.raises(_Boom):
            mgr.save(2, arg_params=arrs, blocking=True)
        mgr._test_hooks = {}
        assert mgr.latest_step() == 1, hook
        # a FRESH manager (new process equivalent) sees the same truth
        assert CheckpointManager(tmp_path).latest_step() == 1
        snap = CheckpointManager(tmp_path).restore()
        np.testing.assert_array_equal(snap.arrays["arg:w"], arrs["w"])
    # async path surfaces the writer error on wait_until_finished
    mgr._test_hooks = {"before_marker": _boom}
    mgr.save(3, arg_params=arrs, blocking=False)
    with pytest.raises(_Boom):
        mgr.wait_until_finished()
    mgr._test_hooks = {}
    assert mgr.latest_step() == 1
    mgr.close()


def test_capture_survives_donated_buffer_deletion(tmp_path):
    """The fused step executor and optimizer donate their input buffers
    (donate_argnums), so the training step AFTER an async save may delete
    the very device arrays the snapshot references. capture() must land
    everything on the host before save() returns."""
    import jax.numpy as jnp
    mgr = CheckpointManager(tmp_path)
    w = jnp.arange(8, dtype=jnp.float32)
    mgr.save(1, arg_params={"w": w})
    w.delete()                      # what donation does to the source buffer
    mgr.wait_until_finished()       # would raise 'array deleted' pre-fix
    assert mgr.latest_step() == 1
    np.testing.assert_array_equal(mgr.restore().arrays["arg:w"],
                                  np.arange(8, dtype=np.float32))
    mgr.close()


def test_async_writer_error_surfaces_on_next_save(tmp_path):
    """A failed async write must not stay silent until wait_until_finished:
    the next save() re-raises it, then clears it so saving can continue."""
    mgr = CheckpointManager(tmp_path)
    arrs = {"w": np.ones(2, np.float32)}
    mgr._test_hooks = {"before_write": _boom}
    mgr.save(1, arg_params=arrs)          # async; writer fails in background
    mgr._queue.join()
    mgr._test_hooks = {}
    with pytest.raises(_Boom):
        mgr.save(2, arg_params=arrs)
    mgr.save(2, arg_params=arrs, blocking=True)   # error consumed; works
    assert mgr.latest_step() == 2
    mgr.close()


def test_async_writer_error_surfaces_at_close(tmp_path):
    """close() is often the LAST manager call a trainer makes — a writer
    error still latched there must re-raise, not vanish with the thread."""
    mgr = CheckpointManager(tmp_path)
    mgr._test_hooks = {"before_write": _boom}
    mgr.save(1, arg_params={"w": np.ones(2, np.float32)})
    mgr._queue.join()
    with pytest.raises(_Boom):
        mgr.close()
    mgr.close()                            # idempotent after surfacing


def test_unclosed_manager_with_writer_error_audited_at_exit(tmp_path):
    """A trainer that never calls close()/wait_until_finished() after a
    failed async save must still hear about it: the atexit audit logs the
    unraised writer error(s) so 'my last checkpoints silently never
    committed' can't happen."""
    script = r"""
import sys
import numpy as np
from mxtpu.checkpoint import CheckpointManager


def _boom():
    raise RuntimeError("disk on fire")


mgr = CheckpointManager(sys.argv[1])
mgr._test_hooks = {"before_write": _boom}
mgr.save(1, arg_params={"w": np.ones(2, np.float32)})
mgr._queue.join()
# exits WITHOUT close() — the audit must speak up
"""
    r = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                       capture_output=True, text=True,
                       env=subprocess_env(), timeout=180)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "unraised async-writer" in r.stderr
    assert "did NOT commit" in r.stderr


def test_writer_retries_transient_fault_then_commits(tmp_path, monkeypatch):
    """An injected transient io_error in the writer thread is absorbed by
    the shared retry policy — the save still commits, and the retry is
    visible in the resilience stats."""
    from mxtpu.resilience import faults
    monkeypatch.setenv(faults.ENV_PLAN, "site=ckpt.write:at=1:kind=io_error")
    monkeypatch.setenv("MXTPU_RETRY_BACKOFF_S", "0.01")
    faults.reset_fault_plan()
    profiler.reset_resilience_stats()
    try:
        mgr = CheckpointManager(tmp_path)
        mgr.save(1, arg_params={"w": np.ones(2, np.float32)}, blocking=True)
        assert mgr.latest_step() == 1
        mgr.close()
    finally:
        monkeypatch.delenv(faults.ENV_PLAN)
        faults.reset_fault_plan()
    stats = profiler.get_resilience_stats()
    assert stats["faults_injected"] == 1 and stats["retries"] == 1


def test_preemption_handler_sigint_opt_in(tmp_path):
    """``include_sigint=True`` (satellite): Ctrl-C gets the same final-save +
    SIG_DFL re-delivery contract as SIGTERM — the process still dies by
    SIGINT, and the final checkpoint is committed."""
    script = r"""
import os, signal, sys, time
import numpy as np
from mxtpu.checkpoint import CheckpointManager
signal.signal(signal.SIGINT, signal.SIG_DFL)   # pristine disposition
mgr = CheckpointManager(sys.argv[1])
mgr.install_preemption_handler(
    state_fn=lambda: {"step": 3,
                      "arg_params": {"w": np.full(2, 9.0, np.float32)}},
    include_sigint=True)
os.kill(os.getpid(), signal.SIGINT)
time.sleep(60)
print("SURVIVED")
"""
    r = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                       capture_output=True, text=True,
                       env=subprocess_env(), timeout=180)
    assert r.returncode == -signal.SIGINT, (r.returncode, r.stderr[-2000:])
    assert "SURVIVED" not in r.stdout
    mgr = CheckpointManager(tmp_path)
    assert mgr.latest_step() == 3
    np.testing.assert_array_equal(mgr.restore().arrays["arg:w"],
                                  np.full(2, 9.0, np.float32))


def test_sigkill_mid_save_subprocess(tmp_path):
    """A real process death (SIGKILL, no cleanup handlers) between the
    staging write and the COMMIT marker: the next process restores the
    previous committed step."""
    script = r"""
import os, signal, sys
import numpy as np
from mxtpu.checkpoint import CheckpointManager
d = sys.argv[1]
mgr = CheckpointManager(d)
arrs = {"w": np.arange(8, dtype=np.float32)}
mgr.save(1, arg_params=arrs, blocking=True)
mgr._test_hooks = {"before_marker": lambda: os.kill(os.getpid(), signal.SIGKILL)}
mgr.save(2, arg_params=arrs, blocking=True)
print("UNREACHABLE")
"""
    r = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                       capture_output=True, text=True,
                       env=subprocess_env(), timeout=180)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr[-2000:])
    assert "UNREACHABLE" not in r.stdout
    mgr = CheckpointManager(tmp_path)
    assert mgr.latest_step() == 1
    snap = mgr.restore()
    np.testing.assert_array_equal(snap.arrays["arg:w"],
                                  np.arange(8, dtype=np.float32))


def test_discovery_ignores_uncommitted_debris(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(2, arg_params={"w": np.zeros(3, np.float32)}, blocking=True)
    # torn dir (renamed, no marker), staging debris, unrelated entries
    os.makedirs(tmp_path / "step-5")
    (tmp_path / "step-5" / "arrays-r0.npz").write_bytes(b"torn")
    os.makedirs(tmp_path / "step-3.tmp")
    os.makedirs(tmp_path / "stepx-7")
    (tmp_path / "step-notanum").mkdir()
    assert mgr.all_steps() == [2]
    assert CheckpointManager(tmp_path).latest_step() == 2
    mgr.close()


def test_retention_gc_max_to_keep_and_keep_period(tmp_path):
    mgr = CheckpointManager(tmp_path, max_to_keep=2, keep_period=4)
    arrs = {"w": np.zeros(2, np.float32)}
    for s in range(1, 10):
        mgr.save(s, arg_params=arrs, blocking=True)
    # newest two (8, 9) plus every 4th (4, 8) survive
    assert mgr.all_steps() == [4, 8, 9]
    on_disk = sorted(e for e in os.listdir(tmp_path) if e.startswith("step-"))
    assert on_disk == ["step-4", "step-8", "step-9"]
    mgr.close()


def test_fit_resume_from_continues_at_epoch_and_nbatch(tmp_path):
    """Mid-epoch save at (epoch=0, nbatch=1); a fresh fit(resume_from=...)
    must skip the done batches and finish bit-identical to the uninterrupted
    run."""
    from mxtpu import io as mxio
    rs = np.random.RandomState(5)
    X = rs.rand(32, 1, 28, 28).astype(np.float32)
    y = rs.randint(0, 10, 32).astype(np.float32)

    def data():
        return mxio.NDArrayIter(X, y, batch_size=8)   # 4 batches, no shuffle

    mgr = CheckpointManager(tmp_path)

    def save_at_batch_1(param):
        if param.epoch == 0 and param.nbatch == 1:
            mgr.save(1, module=mod_a, epoch=0, nbatch=1, blocking=True)

    mod_a = _lenet_module(seed=11)
    mod_a.fit(data(), num_epoch=2, optimizer="sgd",
              optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
              batch_end_callback=save_at_batch_1)
    full_run = _params_np(mod_a)

    mod_b = _lenet_module(seed=42)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        mod_b.fit(data(), num_epoch=2, optimizer="sgd",
                  optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
                  resume_from=mgr)
    for v1, v2 in zip(full_run.values(), _params_np(mod_b).values()):
        np.testing.assert_array_equal(v1, v2)
    mgr.close()


def test_fit_resume_from_empty_dir_is_fresh_start(tmp_path):
    from mxtpu import io as mxio
    rs = np.random.RandomState(2)
    X = rs.rand(16, 1, 28, 28).astype(np.float32)
    y = rs.randint(0, 10, 16).astype(np.float32)
    mod = _lenet_module(seed=3)
    mod.fit(mxio.NDArrayIter(X, y, batch_size=8), num_epoch=1,
            optimizer="sgd", optimizer_params={"learning_rate": 0.05},
            resume_from=str(tmp_path))    # nothing committed: plain run


def test_do_checkpoint_with_manager_and_fit_roundtrip(tmp_path):
    from mxtpu import io as mxio
    rs = np.random.RandomState(9)
    X = rs.rand(16, 1, 28, 28).astype(np.float32)
    y = rs.randint(0, 10, 16).astype(np.float32)
    mgr = CheckpointManager(tmp_path)
    mod = _lenet_module(seed=13)
    cb = callback.do_checkpoint(mgr, module=mod)
    mod.fit(mxio.NDArrayIter(X, y, batch_size=8), num_epoch=2,
            optimizer="sgd", optimizer_params={"learning_rate": 0.05,
                                               "momentum": 0.9},
            epoch_end_callback=cb)
    mgr.wait_until_finished()
    assert mgr.all_steps() == [1, 2]
    snap = mgr.restore()
    assert snap.meta["epoch"] == 2        # resume starts at epoch 2
    for k, v in _params_np(mod).items():
        np.testing.assert_array_equal(v, snap.arrays[f"arg:{k}"])
    mgr.close()


def test_preemption_handler_sigterm_final_save(tmp_path):
    mgr = CheckpointManager(tmp_path)
    arrs = {"w": np.full(4, 7.0, np.float32)}
    chained = []
    # a Python-level previous handler must be chained to (SIG_DFL would
    # re-deliver and terminate — covered by the subprocess test below)
    outer = signal.signal(signal.SIGTERM, lambda s, f: chained.append(s))
    try:
        mgr.install_preemption_handler(
            state_fn=lambda: {"step": 5, "arg_params": arrs,
                              "epoch": 1, "nbatch": 2})
        os.kill(os.getpid(), signal.SIGTERM)
        # handler runs at the next bytecode boundary; force it
        signal.raise_signal(signal.SIGTERM) if not mgr.all_steps() else None
    finally:
        signal.signal(signal.SIGTERM, outer)
    assert mgr.latest_step() == 5
    assert chained and chained[0] == signal.SIGTERM
    snap = mgr.restore()
    assert snap.meta["epoch"] == 1 and snap.meta["nbatch"] == 2
    np.testing.assert_array_equal(snap.arrays["arg:w"], arrs["w"])
    mgr.close()


def test_preemption_handler_preserves_default_termination(tmp_path):
    """With SIG_DFL as the previous disposition, the handler must restore it
    and re-deliver after the final save: the preemption notice still kills
    the job, and the checkpoint it saved is committed."""
    script = r"""
import os, signal, sys, time
import numpy as np
from mxtpu.checkpoint import CheckpointManager
mgr = CheckpointManager(sys.argv[1])
mgr.install_preemption_handler(
    state_fn=lambda: {"step": 1,
                      "arg_params": {"w": np.ones(2, np.float32)}})
os.kill(os.getpid(), signal.SIGTERM)
time.sleep(60)
print("SURVIVED")
"""
    r = subprocess.run([sys.executable, "-c", script, str(tmp_path)],
                       capture_output=True, text=True,
                       env=subprocess_env(), timeout=180)
    assert r.returncode == -signal.SIGTERM, (r.returncode, r.stderr[-2000:])
    assert "SURVIVED" not in r.stdout
    mgr = CheckpointManager(tmp_path)
    assert mgr.latest_step() == 1
    np.testing.assert_array_equal(mgr.restore().arrays["arg:w"],
                                  np.ones(2, np.float32))


def test_legacy_layout_compat_roundtrip(tmp_path):
    """model.save_checkpoint's prefix-####.params remains first-class: the
    manager discovers it, restores through the compat loader, and native
    steps win when newer."""
    prefix = str(tmp_path / "legmodel")
    rs = np.random.RandomState(1)
    arg = {"fc_weight": nd.array(rs.rand(4, 3).astype(np.float32))}
    aux = {"bn_mean": nd.array(rs.rand(3).astype(np.float32))}
    mx.model.save_checkpoint(prefix, 2, None, arg, aux)

    mgr = CheckpointManager(tmp_path, legacy_prefix=prefix)
    assert mgr.all_steps() == [2]
    snap = mgr.restore()
    assert snap.meta.get("legacy") is True
    np.testing.assert_array_equal(snap.arrays["arg:fc_weight"],
                                  arg["fc_weight"].asnumpy())
    np.testing.assert_array_equal(snap.arrays["aux:bn_mean"],
                                  aux["bn_mean"].asnumpy())
    # the file itself still loads through the original surface
    _sym, arg2, aux2 = mx.model.load_checkpoint(prefix, 2)
    np.testing.assert_array_equal(arg2["fc_weight"].asnumpy(),
                                  arg["fc_weight"].asnumpy())
    # a newer native step shadows the legacy epoch
    mgr.save(3, arg_params={"fc_weight": arg["fc_weight"]}, blocking=True)
    assert mgr.all_steps() == [2, 3] and mgr.latest_step() == 3
    mgr.close()


def test_legacy_discovery_five_digit_epoch(tmp_path):
    """save_legacy writes {epoch:04d}, which is 5+ digits for epoch >=
    10000 — discovery must still find those files."""
    prefix = str(tmp_path / "leg")
    arg = {"w": nd.array(np.ones(2, np.float32))}
    mx.model.save_checkpoint(prefix, 12345, None, arg, {})
    mgr = CheckpointManager(tmp_path, legacy_prefix=prefix)
    assert mgr.all_steps() == [12345]
    snap = mgr.restore()
    np.testing.assert_array_equal(snap.arrays["arg:w"],
                                  np.ones(2, np.float32))
    mgr.close()


def test_multiprocess_layout_rank_files(tmp_path):
    """Single-process stand-in for the multi-process contract: per-rank
    array files, meta/commit by rank 0, restore prefers this rank's file."""
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, arg_params={"w": np.ones(3, np.float32)}, blocking=True)
    step_dir = tmp_path / "step-1"
    assert (step_dir / "arrays-r0.npz").exists()
    assert (step_dir / "meta.json").exists()
    assert (step_dir / "COMMIT").exists()
    meta = json.loads((step_dir / "meta.json").read_text())
    assert meta["process_count"] == 1
    mgr.close()


def test_profiler_checkpoint_counters(tmp_path):
    profiler.reset_checkpoint_stats()
    mgr = CheckpointManager(tmp_path)
    arrs = {"w": np.zeros((256, 256), np.float32)}
    mgr.save(1, arg_params=arrs, blocking=True)
    mgr.save(2, arg_params=arrs)
    mgr.wait_until_finished()
    mgr.restore()
    s = profiler.get_checkpoint_stats()
    assert s["saves"] == 2 and s["commits"] == 2 and s["restores"] == 1
    assert s["committed_bytes"] > 2 * 256 * 256 * 4
    assert s["save_latency_ms_last"] > 0 and s["blocked_step_ms_last"] >= 0
    # the counters ride profiler.dumps() like the compile-cache block
    blob = json.loads(profiler.dumps())
    assert blob["checkpoint"]["commits"] == 2
    mgr.close()


def test_sharding_spec_saved_and_restored(tmp_path):
    """A dp-sharded param round-trips with its NamedSharding spec re-applied
    (8 virtual CPU devices from conftest)."""
    import jax
    from mxtpu.parallel import shard_batch
    from mxtpu.parallel.mesh import data_parallel_mesh
    mesh = data_parallel_mesh()
    x = shard_batch(nd.array(np.arange(16, dtype=np.float32).reshape(8, 2)),
                    mesh)
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, arg_params={"x": x}, blocking=True)
    meta = json.loads((tmp_path / "step-1" / "meta.json").read_text())
    assert meta["shardings"]["arg:x"][0] is not None
    snap = mgr.restore()
    from mxtpu.checkpoint.snapshot import restored_array
    placed = restored_array(snap, "arg:x", mesh)
    from jax.sharding import NamedSharding
    assert isinstance(placed.sharding, NamedSharding)
    assert tuple(placed.sharding.spec)[0] == mesh.axis_names[0]
    np.testing.assert_array_equal(np.asarray(jax.device_get(placed)),
                                  np.arange(16, dtype=np.float32).reshape(8, 2))
    mgr.close()


def test_bfloat16_roundtrip(tmp_path):
    import jax.numpy as jnp
    w = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3),
                 dtype="bfloat16")
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, arg_params={"w": w}, blocking=True)
    got = mgr.restore().arrays["arg:w"]
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(got.astype(np.float32),
                                  w.asnumpy().astype(np.float32))
    mgr.close()


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------


def test_nd_save_is_atomic_on_failure(tmp_path, monkeypatch):
    """A failure (stand-in for a kill) mid-nd.save leaves the OLD file
    intact and no tempfile debris."""
    path = str(tmp_path / "state.params")
    v1 = {"w": nd.array(np.ones(4, np.float32))}
    nd.save(path, v1)

    real_savez = np.savez

    def torn_savez(f, **kw):
        f.write(b"partial garbage")
        raise OSError("simulated kill mid-write")

    monkeypatch.setattr(np, "savez", torn_savez)
    with pytest.raises(OSError):
        nd.save(path, {"w": nd.array(np.zeros(4, np.float32))})
    monkeypatch.setattr(np, "savez", real_savez)

    got = nd.load(path)
    np.testing.assert_array_equal(got["w"].asnumpy(), np.ones(4, np.float32))
    assert not [e for e in os.listdir(tmp_path) if e.endswith(".tmp")]
    # reference-format writes go through the same primitive
    nd.save(path, v1, fmt="reference")
    np.testing.assert_array_equal(nd.load(path)["w"].asnumpy(),
                                  np.ones(4, np.float32))


def test_trainer_save_states_atomic_and_dict_roundtrip(tmp_path):
    b = _batch()
    mod = _lenet_module()
    for _ in range(2):
        mod.forward_backward(b)
        mod.update()
    tr = mod._trainer
    fname = str(tmp_path / "opt.states")
    tr.save_states(fname)
    d1 = tr.states_dict()
    tr2 = _lenet_module(seed=23)._trainer
    tr2.load_states(fname)
    d2 = tr2.states_dict()
    assert d1["num_update"] == d2["num_update"]
    for i, sts in d1["states"].items():
        for a, b_ in zip(sts, d2["states"][i]):
            np.testing.assert_array_equal(a, b_)
    assert not [e for e in os.listdir(tmp_path) if e.endswith(".tmp")]


def test_load_checkpoint_warns_on_unknown_keys(tmp_path):
    prefix = str(tmp_path / "m")
    nd.save(f"{prefix}-0001.params",
            {"arg:w": nd.array(np.ones(2, np.float32)),
             "stray_key": nd.array(np.zeros(2, np.float32))})
    with pytest.warns(UserWarning, match="stray_key"):
        _sym, arg, _aux = mx.model.load_checkpoint(prefix, 1)
    assert "stray_key" in arg              # still honored, loudly


class _AmpSymbol:
    """Fake symbol whose graph contains an amp_cast node."""

    def tojson(self):
        return json.dumps({
            "nodes": [
                {"op": "null", "name": "data", "inputs": []},
                {"op": "amp_cast", "name": "cast0",
                 "attrs": {"dtype": "float16"}, "inputs": [[0, 0, 0]]},
                {"op": "null", "name": "w", "inputs": []},
                {"op": "FullyConnected", "name": "fc",
                 "attrs": {"num_hidden": "4"},
                 "inputs": [[1, 0, 0], [2, 0, 0]]},
            ],
            "arg_nodes": [0, 2],
            "heads": [[3, 0, 0]],
        })


def test_save_checkpoint_honors_remove_amp_cast(tmp_path):
    prefix = str(tmp_path / "amp")
    mx.model.save_checkpoint(prefix, 1, _AmpSymbol(), {}, {},
                             remove_amp_cast=True)
    g = json.loads(open(f"{prefix}-symbol.json").read())
    ops = [n["op"] for n in g["nodes"]]
    assert "amp_cast" not in ops
    fc = next(n for n in g["nodes"] if n["op"] == "FullyConnected")
    # fc's first input rewired to the cast's producer (data, now index 0)
    assert fc["inputs"][0][0] == g["nodes"].index(
        next(n for n in g["nodes"] if n["name"] == "data"))
    # the flag can also preserve the cast nodes
    mx.model.save_checkpoint(prefix, 1, _AmpSymbol(), {}, {},
                             remove_amp_cast=False)
    g2 = json.loads(open(f"{prefix}-symbol.json").read())
    assert "amp_cast" in [n["op"] for n in g2["nodes"]]


def test_strip_amp_cast_passthrough_without_amp_nodes():
    src = json.dumps({"nodes": [{"op": "null", "name": "data",
                                 "inputs": []}],
                      "arg_nodes": [0], "heads": [[0, 0, 0]]})
    assert strip_amp_cast(src) == src


def test_speedometer_same_tick_no_zero_division(monkeypatch):
    import mxtpu.callback as cb
    sp = cb.Speedometer(batch_size=4, frequent=2, auto_reset=False)
    monkeypatch.setattr(cb.time, "time", lambda: 1234.5)   # frozen clock
    for nb in range(1, 7):
        sp(cb.BatchEndParam(epoch=0, nbatch=nb, eval_metric=None))
    # reaching here without ZeroDivisionError is the assertion


def test_async_handoff_blocks_less_than_write(tmp_path):
    """The async contract: the training-thread handoff is much cheaper than
    the full serialize+fsync+commit (no run on the chip has measured the
    share; here we assert the ordering on a meaningful payload)."""
    profiler.reset_checkpoint_stats()
    rs = np.random.RandomState(0)
    arrs = {f"w{i}": rs.rand(128, 1024).astype(np.float32)
            for i in range(8)}           # ~4 MB
    mgr = CheckpointManager(tmp_path, max_to_keep=1)
    mgr.save(1, arg_params=arrs)
    mgr.wait_until_finished()
    s = profiler.get_checkpoint_stats()
    assert s["blocked_step_ms_last"] < s["save_latency_ms_last"]
    mgr.close()


def test_zero_sharded_slots_roundtrip_and_reshard(tmp_path):
    """ZeRO-1 interop: 1/N-sharded optimizer slots captured by snapshot
    round-trip bit-exact, and a restore onto a DIFFERENT dp size re-shards
    (strip old pad, re-pad, re-place) instead of crashing."""
    import jax
    from mxtpu import parallel

    rs = np.random.RandomState(21)
    X = nd.array(rs.randn(16, 6).astype(np.float32))
    y = nd.array(rs.randint(0, 3, 16).astype(np.float32))
    batch = DataBatch(data=[X], label=[y])

    def make(ndev):
        parallel.set_default_mesh(parallel.make_mesh((ndev,), ("dp",)))
        mx.rng.seed(21)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="tanh", in_units=6),
                nn.Dense(3, in_units=8))
        net.initialize(init=mx.initializer.Xavier())
        mod = mx.Module(net, data_names=("data",),
                        label_names=("softmax_label",))
        mod.bind(data_shapes=[DataDesc("data", (16, 6))],
                 label_shapes=[DataDesc("softmax_label", (16,))])
        mod.init_params()
        mod.init_optimizer(kvstore="device", optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        return mod

    try:
        mod8 = make(8)
        for _ in range(2):
            mod8.forward_backward(batch)
            mod8.update()
        lay8 = mod8._trainer._zero_layout
        assert lay8 is not None and lay8.dp == 8
        mom8 = np.asarray(jax.device_get(mod8._trainer._zero_states[0][0]))
        mgr = CheckpointManager(tmp_path)
        mgr.save(2, module=mod8, trainer=mod8._trainer, blocking=True)
        mgr.close()
        meta = json.loads((tmp_path / "step-2" / "meta.json").read_text())
        assert meta["trainer"]["zero"]["layout"]["dp"] == 8
        # the sharded slot's spec is recorded like any other array's
        assert meta["shardings"]["zopt:0:0"] == ["dp"]

        # same dp: bit-exact slot restore through the staged adoption
        mod8b = make(8)
        CheckpointManager(tmp_path).restore(module=mod8b,
                                            trainer=mod8b._trainer)
        assert mod8b._trainer._zero_restore is not None
        from mxtpu.step_cache import StepExecutor
        se = StepExecutor(mod8b._block, mod8b._loss, mod8b._trainer)
        se._ensure_placed()
        se._ensure_zero_states()
        mom8b = np.asarray(jax.device_get(mod8b._trainer._zero_states[0][0]))
        np.testing.assert_array_equal(mom8b, mom8)

        # different dp (4): re-shards, keeps the unpadded content, trains on
        mod4 = make(4)
        CheckpointManager(tmp_path).restore(module=mod4,
                                            trainer=mod4._trainer)
        mod4.forward_backward(batch)      # builds layout + adopts the slots
        lay4 = mod4._trainer._zero_layout
        assert lay4.dp == 4
        s0 = mod4._trainer._zero_states[0][0]
        assert s0.sharding.shard_shape(s0.shape) == (lay4.buckets[0].padded
                                                     // 4,)
        mod4.update()
        l = float(mod4._loss_val.mean().data)
        assert np.isfinite(l)
    finally:
        parallel.set_default_mesh(None)
