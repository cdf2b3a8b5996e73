"""Model step (train), program counter: over the profiled steps and the
expert layers, the tokens that chose the busiest expert over the even share
``tokens * k / E`` (the expert layers' ``count`` state, handed to
``moe.STEP_COUNTS`` by the cell's trainer). With every expert held the pairs
of a step are constant and ``moe_held_load_gap.train`` reads 0 by
construction; this is the straggler among the 32 groups."""
import lfm2


def read(view):
    return lfm2.expert_load_max(view)
