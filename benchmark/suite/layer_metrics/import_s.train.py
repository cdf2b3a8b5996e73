"""Set-up: seconds of the package's own import, ``import/mxtpu`` (the first
line of ``mxtpu/__init__.py`` to its last) less ``import/jax`` inside it (0
where the caller had imported JAX, as ``run.py`` has): the package's lines,
not JAX's and not the backend's start."""
import phases


def read(view):
    return phases.span_seconds(view, ("import/mxtpu",), less=("import/jax",))
