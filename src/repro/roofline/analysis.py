"""Three-term roofline from a compiled (dry-run) XLA executable.

CPU containers cannot measure TPU wall time, so the perf report is *derived*
from the compiled artifact:

  compute    = HLO_FLOPs        / peak_FLOPs_per_chip
  memory     = HLO_bytes        / HBM_bandwidth_per_chip
  collective = collective_bytes / ICI_link_bandwidth

``cost_analysis()`` on a GSPMD-partitioned executable reports *per-device*
FLOPs and bytes; likewise the post-partition HLO text contains per-device
shapes, so every term is already per-chip — no division by chip count.

collective_bytes is NOT in cost_analysis: we parse the compiled HLO and sum
the output-shape bytes of every ``all-gather`` / ``all-reduce`` /
``reduce-scatter`` / ``all-to-all`` / ``collective-permute`` op. For
all-reduce we charge 2x (reduce-scatter + all-gather wire traffic of a ring
implementation); others are charged at output size. This is a lower bound on
wire bytes (ring chunking overheads ignored) but exact enough to rank
bottlenecks and measure optimization deltas.

MODEL_FLOPS uses the standard 6·N·D estimate (N = params — active params for
MoE — and D = tokens processed); the ratio MODEL_FLOPS / HLO_FLOPs exposes
remat recompute and padding waste.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional

# --------------------------------------------------------------------------- #
# hardware model (TPU v5e)
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12        # bf16 FLOP/s per chip
    hbm_bw: float = 819e9             # bytes/s per chip
    ici_bw: float = 50e9              # bytes/s per ICI link
    dcn_bw: float = 6.25e9            # bytes/s per chip, cross-pod
    hbm_bytes: float = 16e9           # HBM capacity per chip


V5E = Hardware()

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# one HLO shape literal, e.g. bf16[16,512]{1,0} or f32[] or s32[8]
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# an op definition line: "%name = <shape-or-tuple> opcode(..."
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?))\s+"
    r"([a-z0-9-]+)")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, int]:
    """Sum per-collective-kind output bytes from (post-SPMD) HLO text."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        shape_str, opcode = m.groups()
        # strip fusion/async wrappers: "all-reduce-start", "all-gather-done"
        base = opcode
        for suffix in ("-start", "-done"):
            if base.endswith(suffix):
                base = base[: -len(suffix)]
        if base not in _COLLECTIVES:
            continue
        if opcode.endswith("-done"):
            continue                       # counted at -start
        out[base] += _shape_bytes(shape_str)
        counts[base] += 1
    out["__counts__"] = counts  # type: ignore[assignment]
    return out


def collective_wire_bytes(col: Dict[str, int]) -> int:
    """Ring-model wire traffic: all-reduce charged 2x, others 1x."""
    total = 0
    for kind in _COLLECTIVES:
        mult = 2 if kind == "all-reduce" else 1
        total += mult * col.get(kind, 0)
    return total


# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    hlo_flops: float                  # per chip
    hlo_bytes: float                  # per chip (HBM traffic)
    collective_bytes: float           # per chip (wire)
    collectives: Dict[str, int]
    collective_counts: Dict[str, int]
    model_flops_total: float          # 6·N·D, whole job
    bytes_per_device: Optional[float] = None   # from memory_analysis
    hw: Hardware = V5E
    cross_pod_bytes: float = 0.0      # collective bytes crossing the pod axis

    # ---- the three terms, in seconds ---------------------------------- #
    @property
    def t_compute(self) -> float:
        return self.hlo_flops / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        t = self.collective_bytes / self.hw.ici_bw
        if self.cross_pod_bytes:
            t += self.cross_pod_bytes / self.hw.dcn_bw
        return t

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def model_flops_per_chip(self) -> float:
        return self.model_flops_total / max(self.n_chips, 1)

    @property
    def useful_flop_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (per chip). >1 => XLA undercounts;
        <1 => remat/recompute/padding waste."""
        if self.hlo_flops == 0:
            return 0.0
        return self.model_flops_per_chip / self.hlo_flops

    @property
    def step_time(self) -> float:
        """Roofline step time (max of the three overlapping terms)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline step time."""
        if self.step_time == 0:
            return 0.0
        return self.model_flops_per_chip / self.hw.peak_flops / self.step_time

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "n_chips": self.n_chips,
            "hlo_flops_per_chip": self.hlo_flops,
            "hlo_bytes_per_chip": self.hlo_bytes,
            "collective_bytes_per_chip": self.collective_bytes,
            "cross_pod_bytes": self.cross_pod_bytes,
            "collectives": {k: v for k, v in self.collectives.items()},
            "collective_counts": self.collective_counts,
            "model_flops_total": self.model_flops_total,
            "bytes_per_device": self.bytes_per_device,
            "xla_flops": getattr(self, "xla_flops", None),
            "xla_bytes": getattr(self, "xla_bytes", None),
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "useful_flop_ratio": self.useful_flop_ratio,
            "mfu_at_roofline": self.mfu,
        }

    def summary(self) -> str:
        return (f"{self.arch:28s} {self.shape:12s} {self.mesh:10s} "
                f"comp={self.t_compute * 1e3:9.3f}ms "
                f"mem={self.t_memory * 1e3:9.3f}ms "
                f"coll={self.t_collective * 1e3:9.3f}ms "
                f"dom={self.dominant:10s} "
                f"useful={self.useful_flop_ratio:6.3f} "
                f"mfu={self.mfu * 100:5.1f}%")


# --------------------------------------------------------------------------- #
def model_flops(cfg, shape_cfg) -> float:
    """6·N_active·D total FLOPs for the step the shape lowers."""
    n = cfg.active_param_count()
    if shape_cfg.kind == "decode":
        tokens = shape_cfg.global_batch          # one new token per sequence
    else:
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
    mult = 6.0 if shape_cfg.kind == "train" else 2.0
    return mult * n * tokens


def analyze(compiled, *, arch: str, shape_name: str, mesh_name: str,
            n_chips: int, model_flops_total: float,
            hw: Hardware = V5E, pod_axis_chips: int = 0) -> RooflineReport:
    """Build a RooflineReport from a compiled executable.

    FLOPs/bytes/collective bytes come from the trip-count-aware HLO walk in
    :mod:`repro.roofline.hlo_cost` — ``compiled.cost_analysis()`` counts
    ``lax.scan`` bodies once and so undercounts an L-layer scanned model by
    ~L x. The XLA numbers are kept in the record as a cross-check.
    """
    from repro.roofline.hlo_cost import hlo_cost

    cost = compiled.cost_analysis()
    if isinstance(cost, list):                    # older jax returns [dict]
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    c = hlo_cost(hlo)
    flops, byts = c.flops, c.bytes
    col = {k: v for k, v in c.coll.items()}
    counts = {k: v for k, v in c.coll_counts.items()}
    wire = collective_wire_bytes(col)

    ma = compiled.memory_analysis()
    mem = float(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)

    rep = RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_name, n_chips=n_chips,
        hlo_flops=flops, hlo_bytes=byts, collective_bytes=float(wire),
        collectives=col, collective_counts=counts,
        model_flops_total=model_flops_total, bytes_per_device=mem, hw=hw)
    rep.xla_flops = xla_flops            # cross-check (scan bodies counted 1x)
    rep.xla_bytes = xla_bytes
    return rep
