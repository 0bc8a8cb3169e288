"""GPT served by ``ServingEngine``, the program's continuous-batching
engine. The configuration file gives sizes (slots, page size, pages a slot,
pool type); every policy knob of the engine stays at the program's default.
The loop, the window and the numbers are ``perfbench/serve_loop.py``'s.
"""
from __future__ import annotations

import functools

import numpy as np

from perfbench import loader, serve_loop

_gpt = loader.load_module("families", "gpt_train")
WIDTHS, check_widths = _gpt.WIDTHS, _gpt.check_widths


def build(ctx):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.serving import ServingConfig, ServingEngine

    c, e = ctx.config, ctx.config["engine"]
    paddle.seed(ctx.seed31)
    net = GPT(GPTConfig(**{k: c[k] for k in WIDTHS}))
    net.eval()
    if c["dtype"] != "bfloat16":
        raise ValueError(f"dtype {c['dtype']!r}: this family serves bf16")
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(
        num_slots=e["num_slots"], page_size=e["page_size"],
        pages_per_slot=e["pages_per_slot"], kv_dtype=e["kv_dtype"],
        prefix_cache=e["prefix_cache"], decode=e["decode"]))
    return net, eng


def warm_up(ctx, eng) -> None:
    """Every program the window can run: the one tick (its mixed and its
    decode-only branch), and the page copy that a prompt diverging inside
    a cached page sets off."""
    vocab = ctx.config["vocab_size"]
    rng = np.random.default_rng([ctx.seed, 7])
    page = ctx.config["engine"]["page_size"]
    first = rng.integers(0, vocab, 2 * page + page // 2, dtype=np.int32)
    second = first.copy()
    second[page + page // 2:] = (second[page + page // 2:] + 1) % vocab
    for prompt in (first, second):
        eng.submit(prompt, 3)
        eng.run()
    eng.reset_results()


def limits(c: dict) -> dict:
    e = c["engine"]
    return {"vocab_size": c["vocab_size"], "num_slots": e["num_slots"],
            "capacity": e["pages_per_slot"] * e["page_size"]}


def device_state(eng):
    """The page pools, whole: K and V, and the scales of int8 pools."""
    return eng.pool.pools


run = functools.partial(serve_loop.run, build=build, warm_up=warm_up,
                        limits=limits, device_state=device_state)
