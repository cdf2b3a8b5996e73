"""Set-up: seconds inside the trainer's ``train/collect`` span (the eager
forward that materialises the parameters, their placement, the optimizer
state), whatever ran inside it; from the program's totals by span name."""
import scopes


def read(view):
    if "profiled_steps" not in view:
        return None
    row = scopes.span_totals().get("train/collect")
    return row["seconds"] if row else None
