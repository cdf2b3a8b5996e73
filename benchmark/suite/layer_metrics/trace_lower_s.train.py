"""Set-up: seconds JAX spent tracing and lowering the step program
(``jax/trace`` + ``jax/lower`` under ``train/compile``)."""
import scopes


def read(view):
    return scopes.compile_seconds(view, ("jax/trace", "jax/lower"))
