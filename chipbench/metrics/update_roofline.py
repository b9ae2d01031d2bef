"""AdaAlter update kernel (kernels/adaalter_update.py, the flat plane's
``flat_fused_update``): the least time its algorithmic bytes need at the
chip's HBM bandwidth, over its measured time per launch, in %. Silent
where the kernel does not run."""
from chipbench import work


def read(r):
    per_device = work.update_bytes(r.model) * r.workers / r.chips
    return r.roofline("flat_fused_update", per_device)
