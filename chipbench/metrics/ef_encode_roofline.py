"""Error-feedback encode kernel (kernels/sync_fused.py, the flat plane's
``flat_ef_blocks``), once per sync round: algorithmic bytes at the chip's
HBM bandwidth over its measured time per launch, in %. Silent where the
kernel does not run."""
from chipbench import work


def read(r):
    per_device = work.ef_encode_bytes(r.model) * r.workers / r.chips
    return r.roofline("flat_ef_blocks", per_device)
