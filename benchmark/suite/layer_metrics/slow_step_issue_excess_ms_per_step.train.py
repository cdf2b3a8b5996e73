"""Model step (train): what the window's slow steps (over 1.2 medians) spent
in the four issuing spans beyond those spans' median, spread over all window
steps: the part of the stalls in which the HOST thread was late."""
import phases


def read(view):
    return phases.slow_excess_ms_per_step(view, phases.issue_s)
