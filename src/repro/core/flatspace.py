"""FlatSpace: the whole train state as one contiguous, tile-aligned plane.

The paper's wall-time win needs the *per-step* device cost to be small and
the *sync round* to be one cheap collective — but a per-leaf hot path pays
one kernel launch + one pad-to-tile per parameter leaf every step, and one
small collective per leaf every sync round. ``FlatSpace`` is the fix: at
init, every parameter-shaped pytree (params, B² accumulators, error-feedback
residuals, gradient anchors) is packed into ONE fp32 plane per state tensor,

  * **dtype-bucketed**: leaves are ordered so same-dtype leaves are
    contiguous (bf16 params never interleave with fp32 norms), and the
    per-row/per-block ``round16`` sidecars tell the flat kernels where wire
    and parameter values must round through bfloat16 — which is what keeps
    the flat plane *bitwise identical* to the per-leaf layout even though
    the plane itself is fp32 (an fp32 slot holds the bf16 value exactly;
    the kernels re-round after every write, so the next step reads the same
    bits the bf16 leaf would have held);
  * **tile-aligned**: each leaf's slot is padded to ``ALIGN`` (= one
    ``BLOCK_ROWS×128`` update-kernel grid tile) ONCE, at pack time — the
    per-leaf path pays the same pad-to-tile on every single launch;
  * **cheap to view**: ``unpack`` is a slice + reshape + cast per leaf, so
    the model forward consumes ordinary pytrees while the optimizer and the
    sync round run over the plane.

With the planes in place, the fused Local AdaAlter step is one
``pallas_call`` over the whole plane (``kernels.adaalter_update.
flat_fused_update``) instead of L launches, and the error-feedback sync
encode is one kernel plus ONE all-reduce of a single flat wire array
(``kernels.sync_fused.flat_ef_plane`` + :func:`mean_planes`) instead of
2·L small collectives. ``launch/steps.py`` routes both through here under
``OptimizerConfig.flat``.

Invariant the bitwise guarantees lean on: slot padding is zero and *stays*
zero — gradients pack to zero pads, so the update writes
``0 − η·0·rsqrt(B² + t'·ε²) = 0`` back (ε > 0, the paper's setting, keeps
the rsqrt finite on zero pads), and the sync kernel quantizes zero blocks
to zero wire + zero residual. Real elements therefore see exactly the
per-leaf values: slots are aligned to the quantization block, so wire
blocks never straddle leaves or workers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.adaalter_update import BLOCK_ROWS, LANES
from repro.kernels.tiling import padded_size

Pytree = Any

#: default slot alignment: one (BLOCK_ROWS, 128) update-kernel grid tile.
#: Divisible by every quantization block size in use (256 default), so the
#: sync-plane block partition matches the per-leaf one exactly.
ALIGN = BLOCK_ROWS * LANES

#: optimizer-state keys that are per-worker scalars, NOT param-shaped
#: subtrees (the same convention sharding/specs.opt_state_shardings uses).
SCALAR_STATE_KEYS = ("step", "tprime")


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """One leaf's home in the plane (offsets in elements, per batch row)."""

    index: int                 # position in the ORIGINAL tree flatten order
    shape: Tuple[int, ...]     # body shape (batch axes stripped)
    dtype: Any                 # the leaf's true dtype (what unpack restores)
    size: int                  # prod(shape)
    offset: int                # start element within the plane
    padded: int                # slot length (size rounded up to align)


class FlatSpace:
    """Geometry of one packed parameter plane.

    Built once from an abstract (or live) pytree whose leaves all carry the
    same ``batch_ndim`` leading axes (the local-SGD worker axis). All
    parameter-shaped planes (params, b2, residuals, anchors) share this one
    geometry; only their element dtype semantics differ (``unpack`` casts to
    the slot dtypes for params, or to a forced dtype for fp32 state planes).
    """

    def __init__(self, treedef, slots: List[LeafSlot],
                 batch_shape: Tuple[int, ...], align: int,
                 shards: int = 1, eps: Optional[float] = None) -> None:
        if eps is not None and eps <= 0:
            raise ValueError(
                "FlatSpace requires eps > 0: zero slot padding only stays "
                "zero through the update because rsqrt(B² + t'·eps²) is "
                "finite on zero pads — with eps == 0 the pads would train "
                f"on garbage (got eps={eps!r})")
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        self.treedef = treedef
        self.slots = slots                     # in PLANE order (dtype buckets)
        self.batch_shape = batch_shape
        self.batch_ndim = len(batch_shape)
        self.align = align
        self.shards = shards
        # Tail-pad ONLY: slot offsets are independent of the shard count, so
        # the same checkpointed plane reshards across mesh shapes by padding
        # or truncating zero tail elements. Each of the ``shards`` contiguous
        # sub-planes is then a whole number of update-kernel tiles, so every
        # shard boundary lands on a tile (and quantization-block) boundary.
        end = (slots[-1].offset + slots[-1].padded) if slots else 0
        self.plane_size = padded_size(end, shards * align) if end else 0
        self.shard_size = self.plane_size // shards if shards else 0

    # ------------------------------------------------------------------ #
    @classmethod
    def build(cls, tree: Pytree, *, batch_ndim: int = 0,
              align: int = ALIGN, shards: int = 1,
              eps: Optional[float] = None) -> "FlatSpace":
        """Lay out ``tree``'s leaves into dtype buckets of aligned slots.

        ``tree`` may be live arrays or ``ShapeDtypeStruct``s. Leaves are
        grouped by dtype (buckets ordered by dtype name, stable within a
        bucket) so each bucket is one contiguous plane range. With
        ``shards > 1`` the plane gains tail padding so it splits into
        ``shards`` equal tile-aligned sub-planes (slot offsets unchanged).
        """
        assert align % LANES == 0, align
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            raise ValueError("cannot build a FlatSpace over an empty tree")
        batch_shape = tuple(leaves[0].shape[:batch_ndim])
        slots: List[LeafSlot] = []
        order = sorted(range(len(leaves)),
                       key=lambda i: (jnp.dtype(leaves[i].dtype).name, i))
        offset = 0
        for i in order:
            leaf = leaves[i]
            if tuple(leaf.shape[:batch_ndim]) != batch_shape:
                raise ValueError(
                    f"leaf {i} batch axes {leaf.shape[:batch_ndim]} != "
                    f"{batch_shape}")
            dtype = jnp.dtype(leaf.dtype)
            if not jnp.issubdtype(dtype, jnp.floating):
                raise ValueError(f"non-float leaf dtype {dtype} unsupported")
            body = tuple(leaf.shape[batch_ndim:])
            size = int(np.prod(body, dtype=np.int64)) if body else 1
            padded = padded_size(size, align)
            slots.append(LeafSlot(index=i, shape=body, dtype=dtype,
                                  size=size, offset=offset, padded=padded))
            offset += padded
        return cls(treedef, slots, batch_shape, align, shards=shards,
                   eps=eps)

    # ------------------------------------------------------------------ #
    # pack / unpack
    # ------------------------------------------------------------------ #
    def pack(self, tree: Pytree):
        """tree -> fp32 plane of shape ``batch_shape + (plane_size,)``.

        Casts every leaf to fp32 (exact for bf16) and zero-pads each slot —
        the once-per-init pad the per-leaf path re-pays every launch.
        """
        leaves = self.treedef.flatten_up_to(tree)
        parts = []
        for slot in self.slots:
            leaf = leaves[slot.index]
            flat = leaf.astype(jnp.float32).reshape(
                self.batch_shape + (slot.size,))
            if slot.padded != slot.size:
                pad = [(0, 0)] * self.batch_ndim + \
                      [(0, slot.padded - slot.size)]
                flat = jnp.pad(flat, pad)
            parts.append(flat)
        plane = parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)
        tail = self.plane_size - plane.shape[-1]
        if tail:                               # shard-count tail padding
            plane = jnp.pad(plane, [(0, 0)] * self.batch_ndim + [(0, tail)])
        return plane

    def unpack(self, plane, *, dtype: Optional[Any] = None) -> Pytree:
        """plane -> pytree of leaf views (slice + reshape + cast per leaf).

        ``dtype=None`` restores each slot's true dtype (params semantics);
        a concrete dtype (e.g. fp32) overrides it for the accumulator /
        residual / anchor planes, which mirror the param geometry but are
        fp32 state regardless of the param dtypes.
        """
        leaves: List[Any] = [None] * len(self.slots)
        for slot in self.slots:
            seg = plane[..., slot.offset:slot.offset + slot.size]
            leaves[slot.index] = seg.reshape(
                self.batch_shape + slot.shape).astype(dtype or slot.dtype)
        return self.treedef.unflatten(leaves)

    # ------------------------------------------------------------------ #
    # sidecars for the flat kernels (numpy, built once at trace time)
    # ------------------------------------------------------------------ #
    def round16_elems(self) -> np.ndarray:
        """(plane_size,) bool: True where the slot's dtype is 16-bit — the
        elements whose wire/parameter writes must round through bfloat16 to
        stay bitwise identical to the per-leaf layout."""
        mask = np.zeros(self.plane_size, np.bool_)
        for slot in self.slots:
            if jnp.dtype(slot.dtype).itemsize == 2:
                mask[slot.offset:slot.offset + slot.padded] = True
        return mask

    @staticmethod
    def rows_sidecar(elems: np.ndarray, row: int) -> np.ndarray:
        """Per-row (n_rows, 1) fp32 sidecar from a per-element mask; every
        ``row``-element run must be constant (guaranteed by slot alignment,
        since ``row`` divides ``align``)."""
        rows = elems.reshape(-1, row)
        assert (rows == rows[:, :1]).all(), "mask not constant per row"
        return rows[:, :1].astype(np.float32)

    # ------------------------------------------------------------------ #
    # accounting (the bench / dry-run numbers)
    # ------------------------------------------------------------------ #
    @property
    def n_leaves(self) -> int:
        return len(self.slots)

    @property
    def n_real(self) -> int:
        return sum(s.size for s in self.slots)

    @property
    def pad_elems(self) -> int:
        """Padding paid ONCE by the plane (vs once per launch per leaf)."""
        return self.plane_size - self.n_real

    def bucket_ranges(self) -> List[Tuple[str, int, int]]:
        """Contiguous (dtype_name, start, stop) plane ranges, one per
        dtype bucket (the dtype-bucketed layout makes these few)."""
        out: List[Tuple[str, int, int]] = []
        for slot in self.slots:
            name = jnp.dtype(slot.dtype).name
            if out and out[-1][0] == name and out[-1][2] == slot.offset:
                out[-1] = (name, out[-1][1], slot.offset + slot.padded)
            else:
                out.append((name, slot.offset, slot.offset + slot.padded))
        return out


# --------------------------------------------------------------------------- #
# whole-train-state conversion (checkpoint round-trips, restore adapters)
# --------------------------------------------------------------------------- #
def pack_opt_state(fs: FlatSpace, state: Dict[str, Any]) -> Dict[str, Any]:
    """Legacy per-leaf optimizer state -> flat: every param-shaped subtree
    (b2_sync / b2_local / res_* / g_anchor) becomes one fp32 plane; the
    per-worker scalar counters pass through untouched."""
    return {k: (v if k in SCALAR_STATE_KEYS else fs.pack(v))
            for k, v in state.items()}


def unpack_opt_state(fs: FlatSpace, flat_state: Dict[str, Any]
                     ) -> Dict[str, Any]:
    """Inverse of :func:`pack_opt_state`: planes -> fp32 per-leaf subtrees."""
    return {k: (v if k in SCALAR_STATE_KEYS
                else fs.unpack(v, dtype=jnp.float32))
            for k, v in flat_state.items()}


def flat_abstract(fs: FlatSpace, abstract_params: Pytree,
                  abstract_state: Dict[str, Any]):
    """Abstract (ShapeDtypeStruct) flat train state matching what
    :func:`pack_opt_state` produces — the restore template for a
    flat-layout checkpoint."""
    plane = jax.ShapeDtypeStruct(fs.batch_shape + (fs.plane_size,),
                                 jnp.float32)
    del abstract_params  # geometry already captured by fs
    state = {k: (v if k in SCALAR_STATE_KEYS else plane)
             for k, v in abstract_state.items()}
    return plane, state


def adapt_flat_state(plane, flat_state: Dict[str, Any], *,
                     workers: int, plane_size: int):
    """Reshard a restored flat train state across mesh shapes (host-side).

    The plane layout is tail-pad-only (:class:`FlatSpace` with ``shards``),
    so a checkpoint written under one shard count restores under another by
    padding or truncating the trailing zero tail — slot offsets never move.
    Worker-count changes replicate rows (grow) or merge row groups (shrink:
    identical rows pass through exactly, so a grow→shrink round-trip is
    bit-exact; diverged rows fall back to the fp32 mean, the same merge a
    sync round would apply). Scalar counters (step/tprime) replicate on
    grow and take the group head on shrink.
    """
    def _cols(a):
        have = a.shape[-1]
        if have == plane_size:
            return a
        if have < plane_size:
            return np.pad(a, [(0, 0)] * (a.ndim - 1) +
                          [(0, plane_size - have)])
        tail = a[..., plane_size:]
        if np.any(tail):
            raise ValueError(
                f"cannot truncate flat plane {have} -> {plane_size}: "
                "dropped tail is not all-zero (checkpoint was written by an "
                "incompatible slot layout, not just a larger shard pad)")
        return np.ascontiguousarray(a[..., :plane_size])

    def _rows(a, scalar):
        have = a.shape[0]
        if have == workers:
            return a
        if workers % have == 0:
            return np.repeat(a, workers // have, axis=0)
        if have % workers == 0:
            g = a.reshape((workers, have // workers) + a.shape[1:])
            if scalar or bool((g == g[:, :1]).all()):
                return np.ascontiguousarray(g[:, 0])
            return g.mean(axis=1).astype(a.dtype)
        raise ValueError(
            f"cannot reshard {have} checkpointed workers onto {workers}: "
            "one count must divide the other")

    plane = _rows(_cols(np.asarray(plane)), scalar=False)
    state = {}
    for k, v in flat_state.items():
        v = np.asarray(v)
        if k in SCALAR_STATE_KEYS:
            state[k] = _rows(v, scalar=True)
        else:
            state[k] = _rows(_cols(v), scalar=False)
    return plane, state


def is_flat_checkpoint(keys) -> bool:
    """Whether a checkpoint's flat leaf keys (checkpoint/store.py manifest)
    describe the packed-plane layout: params are ONE array (bare '#0' key)
    instead of a subtree ('#0/...')."""
    return any(k == "#0" for k in keys)


# --------------------------------------------------------------------------- #
# the single-collective sync mean
# --------------------------------------------------------------------------- #
@jax.named_scope("worker_mean")
def mean_planes(plane, round16_elems):
    """Cross-worker mean of one wire plane — the ONE collective of a flat
    sync round — bitwise identical to the per-leaf means.

    The mean accumulates in fp32 (exactly what ``jnp.mean`` does for a bf16
    leaf too: it upcasts, accumulates, and rounds the quotient back — pinned
    by tests/test_flat_step.py), then re-rounds the 16-bit slots through
    bfloat16 so the plane keeps holding the exact bits the per-leaf bf16
    mean would have produced.
    """
    from repro.kernels.tiling import round_through_bf16

    m = jnp.broadcast_to(jnp.mean(plane, axis=0, keepdims=True), plane.shape)
    if round16_elems is None or not round16_elems.any():
        return m
    return jnp.where(jnp.asarray(round16_elems), round_through_bf16(m), m)
