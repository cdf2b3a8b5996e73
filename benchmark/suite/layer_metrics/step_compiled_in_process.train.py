"""Set-up: how many step programs XLA compiled in this process (``jax/
cache_miss`` under ``train/compile``) instead of loading them from the
persistent cache (``jax/cache_hit`` there): 0 in a warm run, 1 in a cold one.
Says which of the two a run's ``setup_s`` and step times belong to."""
import scopes


def read(view):
    if "profiled_steps" not in view:
        return None
    totals = scopes.span_totals()
    if "train/first_readback" not in totals:
        return None             # a program that tells no miss from a hit
    row = totals.get("jax/cache_miss", {})
    return row.get("count_by_parent", {}).get("train/compile", 0)
