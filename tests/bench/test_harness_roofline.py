"""Operation and byte counts of the benchmark's yardstick, and its peak
table, pinned against values worked out by hand from the CNN's shapes."""
import pytest

from bench import roofline, system
from bench.spec import resolve


@pytest.fixture(scope="module")
def jobs():
    return {name: system.make_job(resolve(cell).config, resolve(cell).traffic)
            for name, cell in (("cifar10", "cifar10_cnn.paper"),
                               ("femnist", "femnist_cnn.topk_all"))}


def test_parameter_counts(jobs):
    assert jobs["cifar10"].d_params == 545_098
    assert jobs["femnist"].d_params == 428_350


def test_forward_flops_per_sample(jobs):
    # conv1 2*32*32*27*32 + conv2 2*16*16*288*64 + fc1 2*4096*128 + fc2 2*128*10
    assert roofline.cnn_forward_flops(jobs["cifar10"]) == 12_257_792
    # conv1 2*28*28*9*32 + conv2 2*14*14*288*64 + fc1 2*3136*128 + fc2 2*128*62
    assert roofline.cnn_forward_flops(jobs["femnist"]) == 8_495_616


def test_round_model_flops(jobs):
    # 30 clients x 5 epochs x 3 steps x 32, plus 3 references x 5 x 3 x 32
    samples = 30 * 5 * 3 * 32 + 3 * 5 * 3 * 32
    assert roofline.round_model_flops(jobs["cifar10"], 96, 100) == 3 * 12_257_792 * samples


def test_topk_mask_call_bytes():
    line = ('  %k.1 = f32[32,428544]{1,0:T(8,128)} custom-call(%p.60, %p.61), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints='
            '{f32[32,428544]{1,0}, f32[32,1]{1,0}}, frontend_attributes='
            '{kernel_metadata={}}, metadata={op_name="x"}')
    # reads the padded (32, 428544) rows and their thresholds, writes the rows
    assert roofline.custom_call_bytes(line) == 109_707_392


def test_peaks_by_device_kind():
    assert roofline.peaks_for("TPU v5 lite") == {"flops": 197e12,
                                                 "hbm_bytes_per_s": 819e9}
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks_for("TPU v9 imaginary")
