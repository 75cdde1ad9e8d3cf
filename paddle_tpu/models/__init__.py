from paddle_tpu.models.image import (  # noqa: F401
    alexnet,
    googlenet,
    lenet,
    resnet,
    smallnet_mnist_cifar,
    vgg16,
)
from paddle_tpu.models.text import (  # noqa: F401
    hierarchical_lstm_classifier,
    bidi_lstm_tagger,
    linear_crf_tagger,
    rnn_crf_tagger,
    seq2seq_attention,
    seq2seq_attention_decoder,
    stacked_lstm_classifier,
)
from paddle_tpu.models.ctr import ctr_linear, ctr_wide_deep  # noqa: F401
from paddle_tpu.models.gan import GAN, gan_conf  # noqa: F401
from paddle_tpu.models.vae import vae_conf  # noqa: F401
from paddle_tpu.models.mellum import mellum  # noqa: F401
from paddle_tpu.models.kimi import kimi  # noqa: F401
from paddle_tpu.models.phi4flash import phi4flash  # noqa: F401
from paddle_tpu.models.laguna import laguna  # noqa: F401
from paddle_tpu.models.lfm2 import lfm2  # noqa: F401
