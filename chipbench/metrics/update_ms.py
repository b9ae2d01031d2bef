"""AdaAlter update (launch/steps.py ``adaalter_update``: the per-leaf
update or the flat plane's kernel): its device time in one ``local_step``
run, median over the window, in ms. Silent where the program names no
scopes."""
from chipbench import scopes


def read(r):
    return scopes.median_ms(r, "local", {"adaalter_update"})
