"""The benchmark's own copy of the data generator yields, for a seed, the
same arrays as the program's ``make_data`` (at a small size)."""
import numpy as np
import pytest

from bench.datagen import make_fleet_data


@pytest.mark.parametrize("dataset,shape,classes",
                         [("cifar10", (32, 32, 3), 10),
                          ("femnist", (28, 28, 1), 62)])
def test_same_arrays_as_make_data(dataset, shape, classes):
    from repro.configs.base import FLConfig
    from repro.federated import make_data
    seed = 2 ** 31 - 7
    cfg = FLConfig(n_clouds=2, clients_per_cloud=3, ref_samples=8)
    want = make_data(cfg, dataset, seed=seed, n_samples=600,
                     samples_per_client=16)
    got = make_fleet_data(shape, classes, 2, 3, n_samples=600,
                          samples_per_client=16, ref_samples=8, alpha=0.5,
                          seed=seed)
    for field in ("client_x", "client_y", "ref_x", "ref_y", "test_x", "test_y"):
        np.testing.assert_array_equal(getattr(got, field), getattr(want, field))
    assert got.n_classes == want.n_classes == classes
