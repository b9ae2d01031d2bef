"""Sync round (launch/steps.py ``sync``, its ``ef_encode`` and
``worker_mean``): device time of the round's scopes in one ``sync_step``
run, median over the window, in ms. Silent where the program names no
scopes, and where no instruction of the round is left: at one worker
with a lossless wire the mean folds away, and the round is a copy of the
updated B² that the update's scope holds."""
from chipbench import scopes


def read(r):
    return scopes.median_ms(r, "sync", {"sync", "ef_encode", "worker_mean"})
