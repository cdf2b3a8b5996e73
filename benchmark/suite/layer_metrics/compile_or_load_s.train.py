"""Set-up: seconds XLA spent compiling the step program, or loading it from
the persistent cache (``jax/compile`` under ``train/compile``)."""
import scopes


def read(view):
    return scopes.compile_seconds(view, ("jax/compile",))
