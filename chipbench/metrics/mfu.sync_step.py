"""Sync round: one sync step's model FLOPs over (median device span of
``sync_step`` x chips x bf16 peak), in %."""
import statistics


def read(r):
    spans = r.spans_ms("sync")
    toks = [t for k, t in zip(r.kinds, r.tokens) if k == "sync"]
    if not spans or not toks:
        return None
    flops = r.flops_per_token * toks[0]
    return 100.0 * flops / (statistics.median(spans) / 1e3
                            * r.chips * r.peaks["bf16_flops_per_s"])
