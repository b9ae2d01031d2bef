"""Head and loss (models/lstm.py ``head``, models/model.py ``xent``):
device time of the ``head`` and ``xent`` scopes, forward and backward,
in one ``local_step`` run, median over the window, in ms. Silent where
the program names no scopes."""
from chipbench import scopes


def read(r):
    return scopes.median_ms(r, "local", {"head", "xent"})
