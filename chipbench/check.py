"""The comparison that decides ``correct``.

What the timed path produced in its first sync window (steps 1..H, driven
through the window's own ``train_loop`` calls, on the window's batch and
worker count) is compared with the float32 reference (``reference.py``)
on the same seed and batches. Four numbers, each against its own limit
(``limits/<cell>.json``):

* ``loss``   the largest |program - reference| of the loss of steps 1..H
             (mean over workers), in nats;
* ``grad``   the first gradient as the optimizer keeps it, read from the
             state after step 1 (sqrt(sum(B2_local - B2_sync)) per leaf);
* ``update`` the parameters' change from the seeded weights after step
             H-1, the last local step of the window;
* ``sync``   the same after step H, the first sync round (worker mean and,
             on the int8 wire, the error-feedback encode).

``grad``, ``update`` and ``sync`` are taken by the worst (worker, leaf):
|program's norm - reference's norm| over the larger of the reference's
norm of that leaf and the median leaf's (for ``grad``, the median leaf's
norm of the float32 gradient, since the state resolves no element of most
leaves of the full vocabulary). A leaf whose reference gradient
is under a thousandth of the median leaf's moves by round-off alone and
is left out, by that rule and not by name.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import family_module, leaf_names, leaf_norms

NUMBERS = ("loss", "grad", "update", "update_median", "sync")
NOUGHT = 1e-3      # leaves under this share of the median leaf's gradient
FAULTS = ("frozen", "half_batch", "no_exchange")


class ProgramReader:
    """Reads the numbers above from the program's own state, on the
    device, leaf by leaf in the per-leaf layout (a flat plane is viewed
    through the program's own ``to_legacy``)."""

    def __init__(self, programs, model: dict, seed: int):
        self.fam = family_module(model["family"])
        self.model = model
        self.key = jax.random.PRNGKey(seed)
        self.flat = bool(programs.is_flat)
        self._legacy = programs.to_legacy
        # the seeded weights are drawn by a program of their own, as the
        # reference draws them: drawn inside the norm's fusion, the chip's
        # compiler may round a few of them differently
        self._draw = jax.jit(lambda key: self.fam.init(model, key))
        self._grad = jax.jit(self._state_grad)
        self._change = jax.jit(self._param_change)
        self.leaves: List[str] = []

    def _per_leaf(self, params, state):
        if self.flat:
            return self._legacy(params, state)
        return params, state

    def _state_grad(self, params, state):
        _, st = self._per_leaf(params, state)
        return leaf_norms(jax.tree_util.tree_map(
            lambda bl, bs: jnp.sqrt(bl - bs), st["b2_local"], st["b2_sync"]))

    def _param_change(self, params, state, p0):
        p, _ = self._per_leaf(params, state)
        return leaf_norms(p, p0)

    def state_grad(self, params, state) -> np.ndarray:
        return np.asarray(self._grad(params, state))

    def change(self, params, state) -> np.ndarray:
        p, _ = jax.eval_shape(self._per_leaf, params, state)
        self.leaves = leaf_names(p)
        return np.asarray(self._change(params, state, self._draw(self.key)))


def leaf_gaps(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray,
              median: np.ndarray) -> np.ndarray:
    """(workers, leaves) |prog - ref| / max(ref, median leaf of ``median``),
    NaN for a leaf left out and inf where the scale is 0."""
    floor = np.array([np.median(m[k]) if k.any() else 0.0
                      for m, k in zip(median, keep)])[:, None]
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(prog - ref) / np.maximum(ref, floor)
    gap = np.where(np.isnan(gap), np.inf, gap)
    return np.where(keep, gap, np.nan)


def compare(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
            diagnostics: bool = False) -> Dict[str, float]:
    """Both dicts: 'loss' (H,), 'grad'/'update'/'sync' (workers, leaves),
    'leaves' (names, same order); ref also 'grad_true'."""
    if list(prog["leaves"]) != list(ref["leaves"]):
        raise ValueError(f"leaf order differs: {prog['leaves']} vs "
                         f"{ref['leaves']}")
    g = np.asarray(ref["grad_true"], np.float64)
    keep = g >= NOUGHT * np.median(g, axis=1, keepdims=True)
    out = {"loss": float(np.max(np.abs(np.asarray(prog["loss"], np.float64)
                                       - np.asarray(ref["loss"],
                                                    np.float64))))}
    for k in ("grad", "update", "sync"):
        p_, r_ = (np.asarray(prog[k], np.float64),
                  np.asarray(ref[k], np.float64))
        # the state keeps g*g on top of b0^2 in float32, so in most leaves
        # of the full vocabulary it holds no element of the gradient: the
        # floor of ``grad`` is the median leaf's norm of the gradient itself
        gaps = leaf_gaps(p_, r_, keep, g if k == "grad" else r_)
        out[k] = float(np.nanmax(gaps, initial=0.0))
        median = float(np.max(np.nanmedian(gaps, axis=1)))
        if k == "update":
            out["update_median"] = median
        elif diagnostics:
            out[k + "_median"] = median
        if diagnostics:
            gp, gr = np.sqrt((p_ ** 2).sum(1)), np.sqrt((r_ ** 2).sum(1))
            out[k + "_global"] = float(np.max(np.abs(gp - gr) / gr))
    if diagnostics:
        # the share of each leaf's gradient norm that the fp32 state holds
        seen = np.asarray(ref["grad"], np.float64) / g
        out["grad_seen_min"] = float(np.min(np.where(keep, seen, np.inf)))
        out["grad_seen_leaf"] = list(ref["leaves"])[
            int(np.argmin(np.where(keep, seen, np.inf)) % seen.shape[1])]
        if "init" in prog:
            # the program's weights at step 1 against the seeded draw: 0
            # when both start from the same bits
            out["init_gap"] = float(np.max(np.asarray(prog["init"])))
    if not np.isfinite(out["loss"]):
        out["loss"] = float("inf")
    return out


def verdict(numbers: Dict[str, float], limits: Optional[dict]) -> bool:
    """Correct when every number with a limit is at or under it (a NaN is
    over). A limit of null marks a number reported but not compared. No
    limits, no verdict: a cell without a limits file is never correct."""
    if not limits:
        return False
    return all(numbers[k] <= limits[k] for k in NUMBERS
               if limits.get(k) is not None)


def report(numbers: Dict[str, float], limits: Optional[dict]) -> dict:
    lim = limits or {}
    return {k: {"value": numbers[k], "limit": lim.get(k)} for k in NUMBERS}
