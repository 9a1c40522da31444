"""Roofline analysis from compiled dry-run artifacts (no TPU required).

Terms (per chip, seconds), against the published peaks of the chip
kind the caller reckons for (``PEAKS``, keyed by ``Device.device_kind``):
  compute    = HLO_FLOPs_per_device / flops
  memory     = HLO_bytes_per_device / hbm_bw
  collective = Σ per-device collective payload x type-multiplier / ici_bw

Collective bytes are parsed from the partitioned HLO text (SPMD: shapes
are per-device shards; every device executes each collective once).
Type multipliers approximate ring algorithms: all-reduce moves ~2x its
payload per device, all-gather/reduce-scatter ~1x, all-to-all ~1x,
collective-permute 1x. Ops whose replica_groups span pods are counted as
cross-pod (DCI) traffic and priced at the paper's egress rate
($0.09/GB, Eq. 2) — the TPU mapping of cross-cloud cost.
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""
    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    ici_bw: float         # chip-to-chip bytes/s per link


# Keyed by ``jax.Device.device_kind``. TPU v5e ("TPU v5 lite"): Google
# Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of chip-to-chip interconnect over 4 links (50 GB/s each).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

EGRESS_PER_GB = 0.09      # $ (AWS egress, paper §I)


def peaks_for(kind: str) -> ChipPeaks:
    """The peaks of chip ``kind``; a kind with no published entry is an
    error, never a default."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "u1": 1, "s1": 1,
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", )
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{([^}]*(?:\},\{[^}]*)*)\}")

_MULT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
         "all-to-all": 1.0, "collective-permute": 1.0}


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


@dataclass
class CollectiveOp:
    kind: str
    bytes: int
    cross_pod: bool


@dataclass
class RooflineReport:
    arch: str = ""
    shape: str = ""
    mesh: str = ""
    kind: str = ""
    chips: int = 0
    flops_per_device: float = 0.0
    bytes_per_device: float = 0.0
    collective_bytes_per_device: float = 0.0
    cross_pod_bytes_per_device: float = 0.0
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    dominant: str = ""
    model_flops: float = 0.0
    useful_flops_ratio: float = 0.0
    egress_dollars_per_step: float = 0.0
    n_collectives: int = 0
    collectives_by_kind: Dict[str, int] = field(default_factory=dict)
    memory_per_device_bytes: Dict[str, float] = field(default_factory=dict)

    def to_json(self) -> dict:
        return asdict(self)


def _parse_groups_cross_pod(line: str, pod_of: Optional[np.ndarray]) -> bool:
    """True if any replica group (or permute pair) spans >1 pod."""
    if pod_of is None:
        return False
    m = _GROUPS_RE.search(line)
    if m:
        for grp in re.findall(r"\{([0-9, ]+)\}", m.group(0)):
            ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
            if len({int(pod_of[i]) for i in ids if i < len(pod_of)}) > 1:
                return True
        return False
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota tile notation e.g. [16,32]<=[32,16]T(1,0) — decode by
        # materializing the permutation
        try:
            out_shape = [int(x) for x in m.group(1).split(",")]
            in_shape = [int(x) for x in m.group(2).split(",")]
            ids = np.arange(int(np.prod(in_shape))).reshape(in_shape)
            if m.group(3):
                perm = [int(x) for x in m.group(3).split(",")]
                ids = ids.transpose(perm)
            groups = ids.reshape(out_shape)
            for row in groups:
                if len({int(pod_of[i]) for i in np.ravel(row)}) > 1:
                    return True
            return False
        except Exception:
            return True  # conservative
    m = _PAIRS_RE.search(line)
    if m:
        for pair in re.findall(r"\{([0-9, ]+)\}", "{" + m.group(1) + "}"):
            ids = [int(x) for x in pair.replace(" ", "").split(",") if x]
            if len(ids) == 2 and pod_of[ids[0]] != pod_of[ids[1]]:
                return True
    return False


def parse_collectives(hlo_text: str, pod_of: Optional[np.ndarray] = None
                      ) -> List[CollectiveOp]:
    ops: List[CollectiveOp] = []
    seen_starts = set()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        # avoid double counting start/done pairs: count only non-done
        if "-done(" in line:
            continue
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        if b == 0:
            continue
        ops.append(CollectiveOp(kind=kind, bytes=b,
                                cross_pod=_parse_groups_cross_pod(line,
                                                                  pod_of)))
    return ops


def pod_map(mesh) -> Optional[np.ndarray]:
    """device-id -> pod index (None for single-pod meshes)."""
    if "pod" not in mesh.axis_names:
        return None
    pod_axis = list(mesh.axis_names).index("pod")
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    pod_of = np.zeros(ids.size, np.int32)
    for pod in range(mesh.devices.shape[pod_axis]):
        sl = [slice(None)] * mesh.devices.ndim
        sl[pod_axis] = pod
        pod_of[ids[tuple(sl)].ravel()] = pod
    return pod_of


def analyze(compiled, mesh, *, kind: str, arch: str = "", shape: str = "",
            model_flops: float = 0.0) -> RooflineReport:
    """Roofline terms of ``compiled`` on ``mesh``, reckoned for chips of
    ``kind`` (a ``PEAKS`` key, e.g. ``"TPU v5 lite"``)."""
    peaks = peaks_for(kind)
    chips = int(np.prod(list(mesh.shape.values())))
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))

    hlo = compiled.as_text()
    ops = parse_collectives(hlo, pod_map(mesh))
    coll = sum(op.bytes * _MULT[op.kind] for op in ops)
    cross = sum(op.bytes for op in ops if op.cross_pod)
    by_kind: Dict[str, int] = {}
    for op in ops:
        by_kind[op.kind] = by_kind.get(op.kind, 0) + 1

    compute_s = flops / peaks.flops
    memory_s = byts / peaks.hbm_bw
    collective_s = coll / peaks.ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)

    # global egress: each device in the smaller half of a cross-pod group
    # pushes its payload over the DCI once per op
    egress_bytes_global = cross * chips / 2
    egress = egress_bytes_global / (1024 ** 3) * EGRESS_PER_GB

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem[k] = float(getattr(ma, k, 0.0))
    except Exception:
        pass

    useful = model_flops / (flops * chips) if flops else 0.0
    return RooflineReport(
        arch=arch, shape=shape, kind=kind,
        mesh="x".join(f"{k}{v}" for k, v in mesh.shape.items()),
        chips=chips, flops_per_device=flops, bytes_per_device=byts,
        collective_bytes_per_device=coll, cross_pod_bytes_per_device=cross,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=model_flops,
        useful_flops_ratio=useful, egress_dollars_per_step=egress,
        n_collectives=len(ops), collectives_by_kind=by_kind,
        memory_per_device_bytes=mem)
