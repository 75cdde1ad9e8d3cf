"""Model-zoo configs build and produce correct shapes (reference:
config round-trip tests under trainer_config_helpers/tests/configs)."""

import jax
import numpy as np
import pytest

from paddle_tpu.core.arg import id_arg, non_seq
from paddle_tpu.models import (
    alexnet,
    bidi_lstm_tagger,
    googlenet,
    lenet,
    resnet,
    smallnet_mnist_cifar,
    stacked_lstm_classifier,
    vgg16,
)
from paddle_tpu.network import Network


@pytest.mark.parametrize(
    "factory,kwargs,n_classes",
    [
        (lenet, {}, 10),
        (smallnet_mnist_cifar, {}, 10),
        (alexnet, {"image_shape": (224, 224, 3), "num_classes": 100}, 100),
        (vgg16, {"image_shape": (32, 32, 3), "num_classes": 10}, 10),
        (googlenet, {"image_shape": (224, 224, 3), "num_classes": 50}, 50),
        (resnet, {"depth": 50, "image_shape": (64, 64, 3), "num_classes": 10}, 10),
    ],
)
def test_image_models_build(factory, kwargs, n_classes):
    conf = factory(**kwargs)
    net = Network(conf)
    assert net.specs["output"].dim == (n_classes,)


def test_resnet50_param_count():
    conf = resnet(depth=50, image_shape=(224, 224, 3), num_classes=1000)
    net = Network(conf)
    total = sum(
        int(np.prod(pc.dims)) for pc in net.param_confs.values()
    )
    # ResNet-50 has ~25.6M params; allow slack for fc-head differences
    assert 24e6 < total < 27e6, total


def test_resnet_refuses_the_retired_fused_path():
    with pytest.raises(ValueError, match="fused=True"):
        resnet(depth=50, fused=True)


def test_lenet_forward_shape():
    conf = lenet()
    net = Network(conf)
    params = net.init_params(jax.random.key(0))
    feed = {
        "image": non_seq(np.zeros((2, 28, 28, 1), np.float32)),
        "label": id_arg(np.zeros((2,), np.int32)),
    }
    outs, _ = net.forward(params, feed)
    assert outs["output"].value.shape == (2, 10)
    assert outs["cost"].value.shape == (2,)


def test_text_models_build_and_forward():
    conf = stacked_lstm_classifier(vocab_size=100, emb_dim=8, hidden=8,
                                   num_layers=2, num_classes=2)
    net = Network(conf)
    params = net.init_params(jax.random.key(0))
    feed = {
        "words": id_arg(np.zeros((2, 7), np.int32), np.asarray([7, 3])),
        "label": id_arg(np.zeros((2,), np.int32)),
    }
    outs, _ = net.forward(params, feed)
    assert outs["output"].value.shape == (2, 2)

    conf = bidi_lstm_tagger(vocab_size=50, emb_dim=8, hidden=8, num_tags=5)
    net = Network(conf)
    params = net.init_params(jax.random.key(1))
    feed = {
        "words": id_arg(np.zeros((2, 6), np.int32), np.asarray([6, 4])),
        "tags": id_arg(np.zeros((2, 6), np.int32), np.asarray([6, 4])),
    }
    outs, _ = net.forward(params, feed)
    assert outs["output"].value.shape == (2, 6, 5)
