"""TPU tests (backend cross-check, SURVEY §4).

Run on the REAL chip: ``python -m pytest -m tpu tests/ -q``.  On the CPU
mesh these are skipped (conftest); under ``-m tpu`` a missing TPU is a
FAILURE.  Purpose: catch the libtpu-skew / f64-poisoning / donation-layout
classes of breakage in the small API corners ``chip_smoke.py`` (the
full-width end-to-end run) does not touch.
"""
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def on_tpu():
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        pytest.fail(f"`-m tpu` needs a TPU, found {d.platform}/"
                    f"{d.device_kind}")
    return d


def test_lenet_fit_smoke(on_tpu):
    """Small LeNet fit on the chip: loss decreases, eval runs."""
    from deeplearning4j_tpu.datasets import MnistDataSetIterator
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import (ConvolutionLayer,
                                                   DenseLayer, OutputLayer,
                                                   SubsamplingLayer)
    conf = (NeuralNetConfiguration.builder().seed(123).updater(Adam(1e-3))
            .list()
            .layer(ConvolutionLayer.builder().nIn(1).nOut(8)
                   .kernelSize(5, 5).activation("relu").build())
            .layer(SubsamplingLayer.builder().kernelSize(2, 2)
                   .stride(2, 2).build())
            .layer(DenseLayer.builder().nOut(32).activation("relu").build())
            .layer(OutputLayer.builder("negativeloglikelihood").nOut(10)
                   .activation("softmax").build())
            .setInputType(InputType.convolutionalFlat(28, 28, 1)).build())
    net = MultiLayerNetwork(conf).init()
    it = MnistDataSetIterator(64, True, 123, numExamples=256)
    net.fit(it, epochs=1)
    first = net.score()
    net.fit(it, epochs=3)
    assert np.isfinite(first)
    assert net.score() < first


def test_samediff_bf16_step(on_tpu):
    """bf16 SameDiff train step on the MXU: finite loss, f32 masters."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.autodiff.samediff import (SameDiff,
                                                      TrainingConfig)
    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.learning import Adam
    sd = SameDiff.create()
    x = sd.placeholder("x", shape=(None, 16))
    w = sd.var("w", np.random.RandomState(0).randn(16, 4)
               .astype(np.float32) * 0.1)
    label = sd.placeholder("label", shape=(None, 4))
    b = sd.var("b", np.zeros(4, np.float32))
    pred = sd.nn().linear(x, w, b, name="pred")
    sd.loss().meanSquaredError(label, pred, name="loss")
    sd.setTrainingConfig(TrainingConfig(
        updater=Adam(1e-2), dataSetFeatureMapping=["x"],
        dataSetLabelMapping=["label"], dataType="BFLOAT16"))
    rng = np.random.RandomState(1)
    X = rng.randn(32, 16).astype(np.float32)
    Y = (X @ rng.randn(16, 4)).astype(np.float32)
    hist = sd.fit(DataSet(X, Y), epochs=20)
    assert np.isfinite(hist.finalTrainingLoss())
    assert hist.finalTrainingLoss() < 100.0
    # master variable must remain f32 (mixed-precision contract)
    assert sd.getVariable("w").getArr().numpy().dtype == np.float32


def test_donation_layout_stability(on_tpu):
    """Param buffers are donated into the fused step: repeated steps must
    keep shapes/dtypes/values sane (layout churn would break donation)."""
    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.learning import Sgd
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(1e-2))
            .list()
            .layer(DenseLayer.builder().nOut(32).activation("tanh").build())
            .layer(OutputLayer.builder("mse").nOut(2)
                   .activation("identity").build())
            .setInputType(InputType.feedForward(12)).build())
    net = MultiLayerNetwork(conf).init()
    shapes0 = {k: {p: v.shape for p, v in d.items()}
               for k, d in net.params_.items()}
    rng = np.random.RandomState(2)
    ds = DataSet(rng.randn(16, 12).astype(np.float32),
                 rng.randn(16, 2).astype(np.float32))
    for _ in range(10):
        net.fit(ds)
    shapes1 = {k: {p: v.shape for p, v in d.items()}
               for k, d in net.params_.items()}
    assert shapes0 == shapes1
    flat = net.params().numpy()
    assert np.isfinite(flat).all()


def test_bf16_matmul_uses_mxu_numerics(on_tpu):
    """bf16 matmul on the chip shows MXU (not f32) rounding — guards
    against silent f64/f32 poisoning of the compute dtype plumbing."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    a = rng.randn(256, 256).astype(np.float32)
    b = rng.randn(256, 256).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    got = np.asarray(jax.jit(jnp.matmul)(
        jnp.asarray(a, jnp.bfloat16), jnp.asarray(b, jnp.bfloat16))
        .astype(jnp.float32))
    rel = np.abs(got - exact) / np.maximum(np.abs(exact), 1e-3)
    # bf16 inputs: relative error well above f32 eps, well below garbage
    assert 1e-5 < np.median(rel) < 3e-2
