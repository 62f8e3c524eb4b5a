"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (EXPERIMENTS.md §Roofline):

    compute    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

``cost_analysis`` provides FLOPs and bytes-accessed for the whole (SPMD)
program — i.e. per-partition values multiplied by nothing: XLA reports the
per-device program, so we treat them as per-chip and divide by per-chip
peaks.  Collective bytes are NOT in cost_analysis: we parse the
post-partitioning HLO text and sum operand bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.launch.mesh import ChipPeaks, chip_peaks

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[\w\[\],{}]+)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )


def _parse_shape_bytes(type_str: str) -> int:
    """Bytes of one HLO shape string like ``f32[8,128]`` (tuples summed)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output bytes per collective kind from post-SPMD HLO text."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        if not line or "=" not in line:
            continue
        m = re.search(
            r"=\s*(.*?)\s*(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(?:-start)?\(", line)
        if m is None:
            continue
        kind = m.group(2)
        # `-done` ops would double-count their `-start` halves
        if f"{kind}-done" in line.split("=")[1][:80]:
            continue
        nbytes = _parse_shape_bytes(m.group(1))
        out[kind] = out.get(kind, 0) + nbytes
    return out


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    coll_by_kind: Dict[str, int]
    chips: int
    device_kind: str   # the target chip, keyed into CHIP_PEAKS

    def __post_init__(self):
        chip_peaks(self.device_kind)  # an unknown kind raises here

    @property
    def peaks(self) -> ChipPeaks:
        return chip_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks.flops_bf16

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bytes_per_s

    @property
    def t_collective(self) -> float:
        # per-chip collective bytes over one ICI link direction (the
        # bottleneck link on a 2-D torus for ring collectives)
        return self.coll_bytes / self.peaks.ici_bytes_per_s_per_link

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time_lb(self) -> float:
        """Lower bound on step time (perfect overlap): max of the terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_row(self) -> Dict[str, float]:
        return {
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "flops_per_chip": self.flops,
            "hbm_bytes_per_chip": self.hbm_bytes,
            "coll_bytes_per_chip": self.coll_bytes,
        }


def analyze(compiled, chips: int, device_kind: str) -> Roofline:
    """Build the roofline terms from a compiled executable, against the
    peaks of ``device_kind`` (the chip the program targets, which a
    dry-run on host devices must name).

    ``cost_analysis`` reports the per-device (partitioned) program.
    """
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    colls = collective_bytes(compiled.as_text())
    return Roofline(
        flops=flops,
        hbm_bytes=hbm,
        coll_bytes=float(sum(colls.values())),
        coll_by_kind=colls,
        chips=chips,
        device_kind=device_kind,
    )


def model_flops(cfg, shape, kind: str) -> float:
    """MODEL_FLOPS: 6*N*D for training (N = params, dense; N_active MoE),
    2*N*D for prefill, 2*N per token for decode — global, then per chip."""
    n_total = cfg.param_count()
    if cfg.n_experts > 0:
        # active params: replace expert MLPs with top_k experts
        gates = 3 if "gated" in cfg.mlp_act else 2
        expert_p = cfg.n_experts * gates * cfg.d_model * cfg.d_ff
        active_p = n_total - cfg.n_layers * expert_p \
            + cfg.n_layers * cfg.top_k * gates * cfg.d_model * cfg.d_ff
    else:
        active_p = n_total
    tokens = shape.batch * (shape.seq if kind != "decode" else 1)
    mult = 6.0 if kind == "train" else 2.0
    return mult * active_p * tokens
