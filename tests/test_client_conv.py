"""The CNN's 3x3 convolution has two lowerings, picked by the platform the
program is lowered for: XLA's own convolution on the TPU and im2col + GEMM
on the CPU. Both forms compute the same function. Here the CPU runs each
form through ``cnn_apply`` and one vmapped ``local_train`` step, at
``HIGHEST`` precision so that only the order of the sums differs."""
from functools import partial

import jax
import numpy as np
import pytest

from repro.federated import client

SHAPES = [pytest.param((32, 32, 3), 10, id="cifar10"),
          pytest.param((28, 28, 1), 62, id="femnist")]
FORMS = (client._conv_im2col, client._conv_native)


@pytest.fixture
def in_form(monkeypatch):
    """``run(form, fn, *args)``: ``fn(*args)``, jitted afresh, with the
    CNN's convolution in ``form``. A fresh trace reads ``client._conv``
    anew; the traced program is checked to hold the form's op."""
    def run(form, fn, *args):
        monkeypatch.setattr(client, "_conv", form)
        with jax.default_matmul_precision("highest"):
            traced = jax.jit(lambda *a: fn(*a)).trace(*args)
            native = "conv_general_dilated" in str(traced.jaxpr)
            assert native == (form is client._conv_native)
            return jax.device_get(traced.lower().compile()(*args))
    return run


def _inputs(input_shape, n_classes, lead):
    kp, kx, ky = jax.random.split(jax.random.PRNGKey(7), 3)
    params = client.cnn_init(kp, input_shape, n_classes)
    x = jax.random.uniform(kx, lead + input_shape)
    y = jax.random.randint(ky, lead, 0, n_classes)
    return params, x, y


@pytest.mark.parametrize("input_shape,n_classes", SHAPES)
def test_cnn_apply_forms_agree(in_form, input_shape, n_classes):
    params, x, _ = _inputs(input_shape, n_classes, (4,))
    im2col, native = (in_form(form, client.cnn_apply, params, x)
                      for form in FORMS)
    assert im2col.shape == (4, n_classes)
    np.testing.assert_allclose(native, im2col, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("input_shape,n_classes", SHAPES)
def test_vmapped_local_train_step_forms_agree(in_form, input_shape,
                                              n_classes):
    """One SGD step (batch 4 of 4 samples, one epoch) for 2 clients from
    the same global parameters, as the engines vmap it. The body of the
    jitted ``local_train`` is traced, so that no cached trace is reused."""
    params, x, y = _inputs(input_shape, n_classes, (2, 4))
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    step = jax.vmap(partial(client.local_train.__wrapped__, epochs=1,
                            batch=4, lr=0.05), in_axes=(None, 0, 0, 0))
    im2col, native = (in_form(form, step, params, x, y, keys)
                      for form in FORMS)
    for name in params:
        assert im2col[name].shape == (2,) + params[name].shape
        scale = np.abs(im2col[name]).max()
        assert scale > 0, name
        # float32 sums of up to 4 x 32 x 32 products, in another order
        np.testing.assert_allclose(native[name], im2col[name], rtol=1e-4,
                                   atol=1e-4 * scale, err_msg=name)
