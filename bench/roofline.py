"""Peaks of the chips the benchmark runs on, and the operations and bytes
its roofline and utilisation metrics count, worked out from shapes."""
from __future__ import annotations

import re
from typing import Dict

from bench.reference import Job

# Keyed by ``jax.Device.device_kind``. TPU v5e ("TPU v5 lite"): Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_for(kind: str) -> Dict[str, float]:
    """A kind with no published entry is an error, never a default."""
    if kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


def cnn_forward_flops(job: Job) -> int:
    """Multiply-adds x 2 of one sample's forward pass: two 3x3 SAME convs
    (each followed by a 2x2 pool) and two dense layers."""
    h, w, c = job.input_shape
    c1, c2 = job.conv_channels
    conv1 = 2 * h * w * 9 * c * c1
    conv2 = 2 * (h // 2) * (w // 2) * 9 * c1 * c2
    flat = (h // 4) * (w // 4) * c2
    return conv1 + conv2 + 2 * flat * job.fc_width + 2 * job.fc_width * job.n_classes


def round_model_flops(job: Job, samples_per_client: int, ref_samples: int) -> int:
    """Forward + backward (3x forward) over every sample a round's
    LocalTrain calls see: the m selected clients and the K reference
    trainings, E epochs of floor(S/B) minibatches of B each. Masked,
    padded or recomputed work does not count."""
    per_client = job.local_epochs * max(1, samples_per_client // job.local_batch) * job.local_batch
    per_ref = job.local_epochs * max(1, ref_samples // job.ref_batch) * job.ref_batch
    samples = job.m_total * per_client + job.n_clouds * per_ref
    return 3 * cnn_forward_flops(job) * samples


_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([0-9,]*)\]")


def shape_bytes(text: str) -> int:
    """Bytes of every array shape written in ``text`` (``f32[30,428350]``)."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _BYTES[dtype]
    return total


def custom_call_bytes(hlo_line: str) -> int:
    """Bytes a kernel call reads and writes, from its HLO line: the result
    shape and the operand shapes in ``operand_layout_constraints``."""
    result = hlo_line.split("=", 1)[1].split("custom-call(", 1)[0]
    if "operand_layout_constraints=" not in hlo_line:
        return shape_bytes(result)
    operands = hlo_line.split("operand_layout_constraints=", 1)[1]
    operands = re.split(r", [a-z_]+=", operands, maxsplit=1)[0]
    return shape_bytes(result) + shape_bytes(operands)
