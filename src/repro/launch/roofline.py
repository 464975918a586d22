"""Three-term roofline analysis from a compiled dry-run artifact.

    compute term    = HLO_FLOPs   / (chips x peak_FLOP/s)
    memory term     = HLO_bytes   / (chips x HBM_bw)
    collective term = coll_bytes  / (chips x link_bw)

``cost_analysis()`` supplies FLOPs/bytes of the (already partitioned,
per-device) program; collective bytes are NOT in cost_analysis, so they are
parsed from the optimized HLO text by summing operand bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

Hardware constants: TPU v5e -- 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = ["HW", "Roofline", "collective_bytes", "compiled_cost",
           "roofline_from_compiled", "model_flops"]


@dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12        # bf16 FLOP/s per chip
    hbm_bw: float = 819e9             # bytes/s per chip
    link_bw: float = 50e9             # bytes/s per ICI link


V5E = HW()

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "u1": 1, "s1": 1,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# Matches the op name right before its '(' -- plain or async '-start' form.
# '-done' ops are skipped (their operand is the in-flight handle, not data).
_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * b


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind operand bytes summed over the per-device program."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        rhs = s.split(" = ", 1)[1]
        m = _COLL_RE.search(rhs)
        if m is None or "-done" in rhs.split("(", 1)[0]:
            continue
        kind = m.group(1)
        # operand shapes appear inside the call parens in optimized HLO text;
        # fall back to the result shape when operands are untyped names.
        shapes = _SHAPE_RE.findall(rhs[m.end():])
        if not shapes:
            shapes = _SHAPE_RE.findall(rhs[: m.start()])
        out[kind] += sum(_shape_bytes(dt, dims) for dt, dims in shapes)
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float                   # per-device FLOPs from cost_analysis
    hlo_bytes: float                   # per-device bytes accessed
    coll_bytes: float                  # per-device collective operand bytes
    coll_breakdown: dict = field(default_factory=dict)
    bytes_per_device: float = 0.0      # peak memory from memory_analysis
    model_flops: float = 0.0           # 6*N*D useful flops (global)
    hw: HW = V5E

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.hw.link_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_fraction(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs x chips): how much compiled compute is useful."""
        tot = self.hlo_flops * self.chips
        return self.model_flops / tot if tot else 0.0

    @property
    def mfu_bound(self) -> float:
        """Roofline-implied MFU upper bound: useful flops / (chips*peak*t_bound)."""
        denom = self.chips * self.hw.peak_flops * self.t_bound
        return self.model_flops / denom if denom else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "bottleneck": self.bottleneck,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes,
            "bytes_per_device": self.bytes_per_device,
            "model_flops": self.model_flops,
            "useful_fraction": self.useful_fraction,
            "mfu_bound": self.mfu_bound,
        }


def compiled_cost(compiled) -> dict:
    """XLA's own accounting of a compiled artifact, as plain floats.

    Normalizes ``compiled.cost_analysis()`` (dict or single-element list
    depending on backend) and ``compiled.memory_analysis()`` into one flat
    record; missing analyses (some backends return None) read as zeros.
    """
    cost = compiled.cost_analysis()
    if isinstance(cost, list):           # some backends return [dict]
        cost = cost[0] if cost else {}
    if cost is None:
        cost = {}
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "temp_bytes": 0.0,
        "argument_bytes": 0.0,
        "output_bytes": 0.0,
        "peak_bytes": 0.0,
    }
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        out["temp_bytes"] = float(getattr(mem, "temp_size_in_bytes", 0.0) or 0.0)
        out["argument_bytes"] = float(
            getattr(mem, "argument_size_in_bytes", 0.0) or 0.0
        )
        out["output_bytes"] = float(
            getattr(mem, "output_size_in_bytes", 0.0) or 0.0
        )
        out["peak_bytes"] = out["temp_bytes"] + out["argument_bytes"]
    return out


def roofline_from_compiled(
    compiled, arch: str, shape: str, mesh_name: str, chips: int,
    model_fl: float, hw: HW = V5E,
) -> Roofline:
    cost = compiled_cost(compiled)
    coll = collective_bytes(compiled.as_text())
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=cost["flops"], hlo_bytes=cost["bytes_accessed"],
        coll_bytes=float(sum(coll.values())), coll_breakdown=coll,
        bytes_per_device=cost["peak_bytes"], model_flops=model_fl, hw=hw,
    )


# ---------------------------------------------------------------------------
# Useful-FLOPs accounting
# ---------------------------------------------------------------------------


def model_flops(cfg, shape, n_params_active: int, kind: str) -> float:
    """MODEL_FLOPS = 6*N*D for training, 2*N*D for a forward-only shape,
    with N = active params (MoE: routed active + shared + dense)."""
    tokens = shape.global_batch * shape.seq_len
    if kind == "train":
        return 6.0 * n_params_active * tokens
    if kind == "prefill":
        return 2.0 * n_params_active * tokens
    # decode: one new token per sequence
    return 2.0 * n_params_active * shape.global_batch
