"""Model step (train): device time per profiled step of the SECOND forwards
of the recomputed blocks, kernels included, by the scope ``jax.checkpoint``
gives them (``.../rematted_computation/block<i>/...``): what a
recomputation rule finer than whole blocks could buy back."""
import jamba


def read(view):
    return jamba.remat_ms(view)
