"""Plain references of the model families, one module each.

A configuration file (``configs/<name>.json``) names its family under
``family``, and the benchmark imports ``chipbench/models/<family>.py``
for it. Each such module provides:

* ``init(model, key)``: the program's seeded weights, drawn bit for bit as
  the program draws them, in their stored dtype. ``check.ProgramReader``
  compares the program's step-1 weights with this draw (``init_gap`` 0).
  The leaves come in the order of the program's per-leaf tree:
  ``check.compare`` raises on a different order.
* ``loss(params, tokens, labels, model, mm)``: the mean next-token loss of
  one worker's batch, in float32, with ``mm`` used for every matrix product
  so that the lower-precision control (``precision.py``) reaches them all.
* ``flops_per_token(model)``: 3 x the forward matmuls' FLOPs per token, for
  ``mfu`` (``work.py``).
* ``small(model)``: the keys to overwrite in ``model`` for the CPU-size copy
  of the benchmark that its own tests run (``tests/test_check.py``).
* ``SMALL_LIMITS``: that size's own limits, ``{number: limit}`` over
  ``check.NUMBERS``, set from CPU readings of sound runs and of the control
  and faults as the chip's limits are.

Nothing here imports the system under test. A new family is added with
its module and a configuration file naming it; no file of the harness
changes.
"""
