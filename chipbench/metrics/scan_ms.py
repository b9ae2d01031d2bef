"""LSTMP scan (models/lstm.py ``lstmp``): device time of each layer's
``lax.scan`` and its residual, forward and backward, in one
``local_step`` run, median over the window, in ms. Silent where the
program names no scopes."""
from chipbench import scopes


def read(r):
    return scopes.median_ms(r, "local", {"lstmp"})
