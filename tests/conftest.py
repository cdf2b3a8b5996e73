"""Test config: force an 8-device CPU "pod simulator" before JAX initializes backends.

This is the NaiveEngine-equivalent deterministic backend of the reference's test
strategy (SURVEY.md §4): CPU is the oracle, and the 8 virtual host devices stand in for
a TPU slice so sharding/collective tests run without real chips. The platform is
pinned through the env AND the jax config (backends are not yet initialized when
conftest loads), so a test run can never reach for an accelerator.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multi_device(n=8): needs an n-device mesh (the XLA "
        "host-device-count spoof above provides 8 virtual CPU devices); "
        "the dp_mesh fixture auto-skips when fewer devices exist")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (`-m 'not slow'`); the full "
        "crash-matrix sweep lives here — run with `-m slow`")


@pytest.fixture
def dp_mesh(request):
    """Shared (n,)-device ``("dp",)`` mesh for sharding/collective tests.

    ``n`` comes from the test's ``@pytest.mark.multi_device(n)`` marker
    (default 8 — the conftest spoof). Skips cleanly when the host exposes
    fewer devices (e.g. a subprocess without the XLA_FLAGS spoof), so
    ≥8-device tests never hard-fail on small hosts."""
    marker = request.node.get_closest_marker("multi_device")
    n = marker.args[0] if marker is not None and marker.args else 8
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices, have {len(jax.devices())}")
    from mxtpu import parallel
    return parallel.make_mesh((n,), ("dp",))


def subprocess_env(virtual_devices: int = 0):
    """Env for test-spawned python children: no TPU claim, no inherited
    8-virtual-device XLA_FLAGS (8 device threads thrash a 1-core VM), repo on
    PYTHONPATH. One copy here so every subprocess test scrubs identically."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    if virtual_devices:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                            f"{virtual_devices}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.fixture
def step_jaxpr_hash():
    """``hash(net, system, x, y)``, as a fixture so that it is found by
    directory (another suite's ``conftest`` may be the imported one)."""
    return _step_jaxpr_hash


def _step_jaxpr_hash(net, system, x, y) -> str:
    """The first 16 hex digits of the hash of the printed jaxpr of loss and
    gradient of a ``HybridDecoderLM`` ``net`` (a benchmark ``system`` module's)
    on tokens ``x`` and targets ``y``: a family's step pinned to the program
    it traced to before the layer spec grew for another family."""
    import hashlib
    import jax.numpy as jnp
    from mxtpu import autograd, nd
    handles = [p for p, _ in system.param_leaves(net)]
    saved = [p._data._data for p in handles]

    def loss_of(ps):
        try:
            for p, v in zip(handles, ps):
                p._data._data = v
            with autograd.pause(train_mode=True):
                out = net(nd.NDArray(jnp.asarray(x)))
                loss = system.system.seq_loss(
                    out, nd.NDArray(jnp.asarray(y, jnp.float32)))
            return jnp.mean(loss.data)
        finally:
            for p, v in zip(handles, saved):
                p._data._data = v

    text = str(jax.make_jaxpr(jax.value_and_grad(loss_of))(saved))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.fixture
def param_names_hash():
    """``hash(net)`` -> ``(hash, listing)``, a fixture for the reason
    ``step_jaxpr_hash`` is one."""
    return _param_names_hash


def _param_names_hash(net):
    """``(first 16 hex digits of the hash, the listing hashed)`` of every
    parameter of ``net`` as ``(attribute path, saved name, shape)``, sorted:
    the path is what the benchmark's ``systems/*.py`` walk with ``getattr``
    to load the reference's weights (``block0/conv/in_proj/weight``), the
    saved name what ``save_parameters`` writes (the root's prefix cut off).
    A renamed child or parameter fails here and not first on the chip."""
    import hashlib
    from mxtpu.gluon.parameter import Parameter
    rows = []

    def walk(block, path):
        for attr, value in vars(block).items():
            if isinstance(value, Parameter):
                name = value.name
                if name.startswith(net.prefix):
                    name = name[len(net.prefix):]
                rows.append((f"{path}{attr}", name, tuple(value.shape)))
        for attr, child in block._children.items():
            walk(child, f"{path}{attr}/")

    walk(net, "")
    listing = "\n".join(f"{p} {n} {s}" for p, n, s in sorted(rows))
    return hashlib.sha256(listing.encode()).hexdigest()[:16], listing
