"""Sync round (core/sync_engine.py): median device span of ``sync_step``
minus that of ``local_step``, in ms: what the round adds to a step."""
import statistics


def read(r):
    sync, local = r.spans_ms("sync"), r.spans_ms("local")
    if not sync or not local:
        return None
    return statistics.median(sync) - statistics.median(local)
