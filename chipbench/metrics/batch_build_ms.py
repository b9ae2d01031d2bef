"""Data layer (repro.data.synthetic via train_loop): host time of one
``make_train_batch`` call, median over the window's steps, in ms."""
import statistics


def read(r):
    return statistics.median(r.batch_ms) if r.batch_ms else None
