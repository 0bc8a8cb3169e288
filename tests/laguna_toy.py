"""What Laguna's test files share: the small model (five layers of hidden
32: full and dense, two sliding, full, sliding; 4 and 6 query heads over 2
key/value heads of 16, a window of 6, 8 experts of which a token takes 3,
pages of 4), its float32 reference and an engine."""
import dataclasses

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import laguna_reference as ref
from paddle_tpu.models.laguna import Laguna, LagunaConfig
from paddle_tpu.serving import ServingConfig, ServingEngine

PAGE = 4


def build(seed=7, **kw):
    paddle.seed(seed)
    net = Laguna(LagunaConfig.tiny(**kw))
    net.eval()
    return net


def layers_of(net):
    layers, _ = net._decode_state()
    for i in range(net.config.num_hidden_layers):
        yield layers[f"layer{i}"]


def reference(net, tokens, control=None, held=None):
    other = net._decode_state()[1]
    config = dataclasses.asdict(net.config)
    got = ref.forward(layers_of(net), other, tokens, config,
                      held=held or net.config.held, control=control)
    got["logits"] = ref.logits(got["state"], other)
    return got


def engine(net, **kw):
    sizes = dict(num_slots=3, page_size=PAGE, pages_per_slot=16,
                 prefix_cache=False)
    sizes.update(kw)
    return ServingEngine(net, ServingConfig(**sizes))


def some_tokens():
    return np.random.default_rng(0).integers(0, 96, 60).astype(np.int32)
