"""Float32 reference of the first sync window of Local AdaAlter training.

It follows the paper's Algorithm 4 (arXiv:1911.09030) for the first H steps
of every worker, from the same seeded weights and on the same token batches
as the system under test:

    eta_t  = lr * min(1, t / warmup)
    x      <- x - eta_t * G / sqrt(B2_sync + t' * eps^2)      (local step t')
    B2     <- B2 + G*G
    x      <- mean over workers of wire(x)                    (after step H)

with B2_sync = b0^2 for the whole first window. Parameters are stored in
the configuration's dtype (each update is computed in float32 and rounded
once on store); ``wire`` is the identity for the fp32 wire, and for the int8
wire the per-block symmetric int8 round trip of the parameter payload
(blocks of ``block`` elements of each worker's flattened leaf, scale =
max|v|/127) rounded to the parameter dtype. The error-feedback residual is
zero before the first round, so it does not enter.

The model's loss and gradients come from ``models/<family>.py``. Nothing
here imports the system under test or takes anything it made: the weights
are drawn again from the seed, and only the token batches are shared.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import precision


def family_module(family: str):
    return importlib.import_module(f"chipbench.models.{family}")


@dataclasses.dataclass(frozen=True)
class Job:
    model: dict          # the configuration file's model keys
    workers: int
    H: int
    lr: float
    warmup: int
    eps: float
    b0: float
    wire: str            # 'fp32' | 'int8'
    block: int = 256


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree, like=None) -> jnp.ndarray:
    """(workers, leaves) float32 norms of worker-stacked leaves, or of their
    difference from ``like`` (unstacked leaves, broadcast over workers)."""
    xs = jax.tree_util.tree_leaves(tree)
    ys = jax.tree_util.tree_leaves(like) if like is not None else [None] * len(xs)
    cols = []
    for x, y in zip(xs, ys):
        d = x.astype(jnp.float32)
        if y is not None:
            d = d - y.astype(jnp.float32)[None]
        cols.append(jnp.sqrt(jnp.sum(jnp.square(d),
                                     axis=tuple(range(1, d.ndim)))))
    return jnp.stack(cols, axis=1)


def int8_roundtrip(x, block: int):
    """Per-block symmetric int8 encode + decode of one worker's leaf."""
    v = x.astype(jnp.float32).reshape(-1)
    pad = (-v.size) % block
    vb = jnp.pad(v, (0, pad)).reshape(-1, block)
    scale = jnp.max(jnp.abs(vb), axis=1, keepdims=True) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(vb / safe), -127.0, 127.0)
    return (q * scale).reshape(-1)[:v.size].reshape(x.shape)


class Reference:
    """Runs the first window for every worker, the workers spread over
    ``devices`` (one worker per device when there are as many)."""

    def __init__(self, job: Job, devices, mm=precision.highest):
        self.job = job
        self.fam = family_module(job.model["family"])
        self.mm = mm
        n = len(devices)
        if job.workers % n:
            devices = devices[:1]
        self.mesh = Mesh(np.array(devices), ("w",))
        self.wsh = NamedSharding(self.mesh, P("w"))
        self.rep = NamedSharding(self.mesh, P())

    # -- pieces, each one jitted program ------------------------------- #
    def _init(self, key):
        return self.fam.init(self.job.model, key)

    def _stack(self, p0):
        R = self.job.workers
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), p0)

    def _grad(self, params, tokens, labels):
        def one(p, t, l):
            p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)
            return jax.value_and_grad(self.fam.loss)(
                p32, t, l, self.job.model, self.mm)
        return jax.vmap(one)(params, tokens, labels)

    def _update(self, params, grads, t, tprime):
        j = self.job
        eta = j.lr * jnp.minimum(1.0, t / j.warmup) if j.warmup > 0 else j.lr
        denom = jnp.sqrt(jnp.float32(j.b0 * j.b0) + tprime * (j.eps * j.eps))
        return jax.tree_util.tree_map(
            lambda x, g: (x.astype(jnp.float32) - eta * g / denom
                          ).astype(x.dtype), params, grads)

    def _sync(self, params):
        j = self.job

        def wire(x):
            if j.wire == "int8":
                w = jax.vmap(lambda r: int8_roundtrip(r, j.block))(x)
                return w.astype(x.dtype).astype(jnp.float32)
            if j.wire == "fp32":
                return x.astype(jnp.float32)
            raise ValueError(f"no reference for the {j.wire!r} wire")

        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                jnp.mean(wire(x), axis=0, keepdims=True).astype(x.dtype),
                x.shape), params)

    @staticmethod
    def _state_grad(grads, b0sq):
        """The gradient as the optimizer's state keeps it after one step:
        sqrt(sum((b0^2 + g^2) - b0^2)) per leaf, in float32."""
        return leaf_norms(jax.tree_util.tree_map(
            lambda g: jnp.sqrt((b0sq + jnp.square(g)) - b0sq), grads))

    # -- the window ------------------------------------------------------ #
    def run(self, seed: int, batches: List[Dict[str, np.ndarray]]) -> dict:
        """batches: the H token batches the program trained on, each
        {'tokens', 'labels'} of shape (workers, batch, seq)."""
        j = self.job
        R, H = j.workers, j.H
        wsh, rep = self.wsh, self.rep
        key = jax.random.PRNGKey(seed)
        p0 = jax.jit(self._init, out_shardings=rep)(key)
        params = jax.jit(self._stack, out_shardings=wsh)(p0)
        grad = jax.jit(self._grad, in_shardings=(wsh, wsh, wsh),
                       out_shardings=(wsh, wsh))
        update = jax.jit(self._update, donate_argnums=(0,),
                         out_shardings=wsh)
        sync = jax.jit(self._sync, donate_argnums=(0,), out_shardings=wsh)
        norms = jax.jit(leaf_norms)
        b0sq = jnp.float32(j.b0 * j.b0)
        out = {"leaves": leaf_names(p0), "loss": []}
        for t in range(1, H + 1):
            b = batches[t - 1]
            toks = jax.device_put(jnp.asarray(b["tokens"]), wsh)
            labs = jax.device_put(jnp.asarray(b["labels"]), wsh)
            losses, grads = grad(params, toks, labs)
            out["loss"].append(float(jnp.mean(losses)))
            if t == 1:
                out["grad_true"] = np.asarray(norms(grads))
                out["grad"] = np.asarray(jax.jit(self._state_grad)(grads, b0sq))
            params = update(params, grads, jnp.float32(t), jnp.float32(t))
            del grads
            if t == H - 1:
                out["update"] = np.asarray(norms(params, p0))
            if t == H:
                params = sync(params)
                out["sync"] = np.asarray(norms(params, p0))
        del params, p0
        return out
