"""Roofline analyzer: HLO collective parsing + pod classification +
term arithmetic on synthetic HLO text."""
import numpy as np
import pytest

from repro.roofline.analyze import (CollectiveOp, _shape_bytes, analyze,
                                    parse_collectives, peaks_for)

HLO = """
HloModule test
ENTRY main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%p0), replica_groups={{0,1},{2,3}}, to_apply=%add
  %ag = bf16[64,512]{1,0} all-gather(%p0), replica_groups={{0,2},{1,3}}, dimensions={0}
  %rs = f32[32]{0} reduce-scatter(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = f32[16,16]{1,0} collective-permute(%p0), source_target_pairs={{0,1},{1,0}}
  %tuple = (f32[8]{0}, f32[8]{0}) all-to-all(%p0, %p0), replica_groups={{0,1}}
  %done = f32[4]{0} all-reduce-done(%ar)
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert _shape_bytes("bf16[64,512]") == 64 * 512 * 2
    assert _shape_bytes("(f32[8]{0}, f32[8]{0})") == 64
    assert _shape_bytes("f32[]") == 4


def test_parse_collectives_kinds_and_bytes():
    ops = parse_collectives(HLO)
    kinds = sorted(o.kind for o in ops)
    assert kinds == ["all-gather", "all-reduce", "all-to-all",
                     "collective-permute", "reduce-scatter"]
    ar = [o for o in ops if o.kind == "all-reduce"][0]
    assert ar.bytes == 128 * 256 * 4


def test_cross_pod_classification():
    # pods: devices 0,1 -> pod 0; devices 2,3 -> pod 1
    pod_of = np.array([0, 0, 1, 1])
    ops = parse_collectives(HLO, pod_of)
    by_kind = {o.kind: o for o in ops}
    assert not by_kind["all-reduce"].cross_pod        # {0,1},{2,3} intra
    assert by_kind["all-gather"].cross_pod            # {0,2} spans pods
    assert by_kind["reduce-scatter"].cross_pod        # {0,1,2,3}
    assert not by_kind["collective-permute"].cross_pod  # 0<->1 same pod


def test_iota_replica_groups():
    hlo = ("%ar = f32[64]{0} all-reduce(%x), "
           "replica_groups=[2,2]<=[4], to_apply=%a\n")
    pod_of = np.array([0, 0, 1, 1])
    ops = parse_collectives(hlo, pod_of)
    assert len(ops) == 1 and not ops[0].cross_pod     # groups {0,1},{2,3}
    pod_of2 = np.array([0, 1, 0, 1])
    assert parse_collectives(hlo, pod_of2)[0].cross_pod


def test_peaks_keyed_by_device_kind():
    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("cpu")


class _Compiled:
    """Stands in for a compiled program: one second of v5e compute and
    one of HBM traffic, plus the collectives of ``HLO``."""

    def cost_analysis(self):
        return {"flops": 197e12, "bytes accessed": 819e9}

    def as_text(self):
        return HLO

    def memory_analysis(self):
        raise NotImplementedError


class _Mesh:
    shape = {"cloud": 2, "client": 2}
    axis_names = ("cloud", "client")


def test_analyze_reckons_for_the_given_kind():
    rep = analyze(_Compiled(), _Mesh(), kind="TPU v5 lite")
    assert rep.kind == "TPU v5 lite" and rep.chips == 4
    assert rep.compute_s == pytest.approx(1.0)
    assert rep.memory_s == pytest.approx(1.0)
    assert rep.n_collectives == 5
    with pytest.raises(ValueError, match="no published peaks"):
        analyze(_Compiled(), _Mesh(), kind="TPU v4")
