"""What the two files of Falcon-H1's tests share: the small model (3 layers
of hidden 64, 4 SSD heads of 8 x 16 in 2 groups beside 4 query heads over 2
key/value heads of 16, pages of 4), its float32 reference and an engine."""
import dataclasses

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import falcon_h1_reference as ref
from paddle_tpu.models.falcon_h1 import FalconH1, FalconH1Config
from paddle_tpu.serving import ServingConfig, ServingEngine

PAGE = 4


def build(seed=7, **kw):
    paddle.seed(seed)
    net = FalconH1(FalconH1Config.tiny(**kw))
    net.eval()
    return net


def layers_of(net):
    layers, _ = net._decode_state()
    for i in range(net.config.num_hidden_layers):
        yield layers[f"layer{i}"]


def reference(net, tokens, control=None, **kw):
    other = net._decode_state()[1]
    config = dataclasses.asdict(net.config)
    got = ref.forward(layers_of(net), other, tokens, config, control=control,
                      **kw)
    got["logits"] = np.asarray(ref.logits(got["state"], other, config))
    return got


def engine(net, **kw):
    sizes = dict(num_slots=3, page_size=PAGE, pages_per_slot=16,
                 prefix_cache=False)
    sizes.update(kw)
    return ServingEngine(net, ServingConfig(**sizes))


def some_tokens():
    return np.random.default_rng(0).integers(0, 96, 60).astype(np.int32)
