"""Train step (launch/steps.py, models/lstm.py): device span of one
``local_step`` program execution, median over the window, in ms."""
import statistics


def read(r):
    spans = r.spans_ms("local")
    return statistics.median(spans) if spans else None
