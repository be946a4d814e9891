"""step_hbm_gib (GiB): arguments plus temp of the compiled decode step,
from its ``memory_analysis()``: what the compiler reserves on the chip
for one decode step, weights and pool included."""


def read(run):
    if run.step_hbm_bytes is None:
        return None
    return run.step_hbm_bytes / 2**30
