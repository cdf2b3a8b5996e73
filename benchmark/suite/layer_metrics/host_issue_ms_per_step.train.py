"""Model step (train): host time per profiled step inside the trainer's
spans ``train/place`` + ``train/prepare`` + ``train/dispatch`` +
``train/adopt``: everything ``dpt.step`` does but wait for the loss."""
import scopes


def read(view):
    spans = scopes.issue_spans(view)
    if spans is None:
        return None
    return sum(b - a for a, b in spans) / view["profiled_steps"] / 1e6
