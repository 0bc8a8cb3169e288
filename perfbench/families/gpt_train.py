"""GPT trained by ``HybridPipelineTrainer``, the program's normal entry
point for compiled training. The configuration file gives sizes, the mesh
and the storage types; every other knob of the trainer stays at the
program's default. The loop, the window and the numbers are
``perfbench/train_loop.py``'s.
"""
from __future__ import annotations

import functools

from perfbench import train_loop

WIDTHS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
          "max_seq_len", "ffn_hidden_size")


def check_widths(c: dict) -> None:
    if c["hidden_size"] != c["num_heads"] * c["head_dim"]:
        raise ValueError(
            f"hidden_size {c['hidden_size']} is not num_heads "
            f"{c['num_heads']} x head_dim {c['head_dim']}")
    if c["ffn_hidden_size"] != 4 * c["hidden_size"]:
        raise ValueError(f"ffn_hidden_size {c['ffn_hidden_size']} is not "
                         f"4 x hidden_size {c['hidden_size']}")


def build(ctx, n_micro: int):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, GPTConfig

    paddle.seed(ctx.seed31)
    model = GPT(GPTConfig(**{k: ctx.config[k] for k in WIDTHS}))
    return train_loop.hybrid_trainer(ctx, model, n_micro)


def limits(c: dict) -> dict:
    return {"vocab_size": c["vocab_size"], "max_seq_len": c["max_seq_len"]}


run = functools.partial(train_loop.run, build=build, limits=limits)
