"""Job kind ``train_model``: job kind ``train`` for any model. The cell's
file names the two modules that know the architecture:

    "modules": {"reference": "reference/<model>.py",
                "system": "systems/<model>.py"}

``reference`` (imports nothing of the program): ``make_weights(cfg, seed,
dtype)`` and ``train_steps(cfg, weights, batches, opt, dtype, row_block=,
precision=)`` returning ``loss``, ``grad_norm`` and ``delta_norm`` (per
leaf). ``system``: ``build_net(cfg, weights, dtype)``, ``param_arrays(net)``,
``Trainer(net, opt)`` with ``place``, ``step``, ``first_gradient_norm``,
``param_arrays``, and ``kernel_path_counts()``. Phases, the window's loop,
the ``observations`` and the comparison are ``jobs/train.py``'s, so the
readers of the training cells read these cells too and the next architecture
adds no job.

On the chip the program's count of kernel paths (which call sites of the
step took Pallas kernels, which an XLA formulation) is printed, and a call
site that took XLA makes the run not ``correct``: a cell that times kernels
must not pass on a silent fall-back.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

import check
import traffic as traffic_mod
from manifest import load_module


def _norms(arrays: dict) -> dict:
    out = {}
    for k, v in arrays.items():
        v = np.ascontiguousarray(v, np.float32).reshape(-1)
        out[k] = float(np.sqrt(np.dot(v, v)))
    return out


def _widest(got: dict, ref: dict, n: int = 5) -> str:
    """The ``n`` leaves whose change differs most from the reference's, as
    ``check.worst_leaf_gap`` measures it; ``check.judge`` names only one."""
    floor = float(np.median(list(ref["delta_norm"].values())))
    gaps = sorted(((abs(got["delta_norm"][k] - r) / max(r, floor), k, r)
                   for k, r in ref["delta_norm"].items()), reverse=True)
    return ", ".join(f"{k} {g:.4g} (reference {r:.4g})"
                     for g, k, r in gaps[:n]) + f"; median leaf {floor:.4g}"


def _module(cell, which: str):
    path = cell.spec["modules"][which]
    return load_module(os.path.join(cell.suite, path),
                       "suite_" + path.replace("/", "_").removesuffix(".py"))


def run(run) -> dict:
    cell, cfg, spec = run.cell, run.cell.config, run.cell.spec
    # the system first: a tree without the model fails here, at once
    system = _module(cell, "system")
    reference = _module(cell, "reference")
    import jax
    import jax.numpy as jnp

    job, opt = spec["job_params"], spec["job_params"]["adam"]
    n_checked = job["checked_steps"]

    with run.phase("inputs"):
        batches = traffic_mod.token_batches(cell.traffic, run.seed,
                                            cfg["vocab_size"])
        B, T = batches[0][0].shape
    with run.phase("weights"):
        weights = reference.make_weights(cfg, run.seed, job["dtype"])
        jax.block_until_ready(weights)

    with run.phase("build"):
        net = system.build_net(cfg, weights, job["dtype"])
        w0 = system.param_arrays(net)
        del weights
        trainer = system.Trainer(net, opt)
        pool = [trainer.place(x, y) for x, y in batches]

    got = {"loss": []}
    with run.phase("compile+first steps"):
        for i in range(n_checked):
            got["loss"].append(
                float(run.call("train/step", trainer.step, *pool[i])))
            run.say(f"step {i + 1} done, loss {got['loss'][-1]:.4f}")
            if i == 0:
                got["grad_norm"] = trainer.first_gradient_norm()
        run.say("reading the parameters back")
        now = trainer.param_arrays()
        got["delta_norm"] = _norms({k: now[k] - w0[k] for k in w0})
        del now, w0
    paths = system.kernel_path_counts()
    run.say(f"kernel paths (call sites, eager and traced): {paths}")
    on_xla = [kind for kind, row in paths.items() if row["xla"]]
    with run.phase("warm-up"):
        # the rest of the pool: same shapes, so nothing compiles; afterwards
        # every batch the window feeds has been through the step once
        for x, y in pool[n_checked:]:
            run.call("train/step", trainer.step, x, y)

    run.window_opens()
    step_s, losses = [], []
    t0 = time.perf_counter()
    while True:
        x, y = pool[len(step_s) % len(pool)]
        t = time.perf_counter()
        losses.append(float(run.call("train/step", trainer.step, x, y)))
        now = time.perf_counter()
        step_s.append(now - t)
        if now - t0 >= run.seconds:
            break
    window_s = now - t0
    run.window_closes()
    run.note_memory("after the window")

    tokens = len(step_s) * B * T
    typical = sorted(step_s)[len(step_s) // 2]
    run.say(f"window: {len(step_s)} steps of {B}x{T} tokens in "
            f"{window_s:.3f}s; loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
            f"step median {typical * 1e3:.1f}ms, longest "
            f"{max(step_s) * 1e3:.1f}ms, "
            f"{sum(s > 1.2 * typical for s in step_s)} over 1.2x the median")
    got["window_loss"] = losses

    obs = {"step_s": step_s, "window_s": window_s, "tokens": tokens,
           "batch": B, "seq_len": T, "chips": cell.chips}
    if run.trace:
        n = min(job["profiled_steps"], 6)
        with run.profile() as prof:
            for i in range(n):
                x, y = pool[i % len(pool)]
                with prof.step("train", i):
                    run.call("train/step", trainer.step, x, y)
        obs["profiled_steps"] = n

    # The plain reference follows the checked steps once the program's state
    # is freed: the peak that the run reports stays the program's, and the
    # set-up time holds nothing of the yardstick's own.
    run.keep_memory_peak()
    del trainer, net, pool, x, y
    gc.collect()

    def follow(precision=None):
        return reference.train_steps(
            cfg, reference.make_weights(cfg, run.seed, job["dtype"]),
            [(jnp.asarray(x), jnp.asarray(y)) for x, y in batches[:n_checked]],
            opt, job["dtype"], row_block=job.get("reference_row_block", B),
            precision=precision)

    with run.phase("reference"):
        ref = follow()
        run.note_memory("after the reference")
    correct = check.judge(check.training_numbers(got, ref), spec["limits"],
                          run.say)
    run.say("widest leaves of delta_norm_gap: " + _widest(got, ref))
    if run.control:
        with run.phase("control"):
            low = follow(check.CONTROL_PRECISION)
            low = dict(low, grad_norm=check.whole_norm(low["grad_norm"]))
            check.judge(check.training_numbers(low, ref), spec["limits"],
                        run.say, what="control")
    if on_xla and not run.rehearsal:
        run.say(f"call sites of {on_xla} took the XLA path on the chip: "
                f"the cell times kernels, so the run is not correct")
        correct = False

    finite = all(np.isfinite(losses))
    return {"correct": bool(correct and finite), "attempted": len(step_s),
            "failed": 0 if finite else 1,
            "end_to_end": {"train_tokens_per_s": tokens / window_s},
            "observations": obs}
