"""A fourth family, for the contract tests alone, shaped as the kind the
harness was opened for: grouped-query attention whose heads are wider than
the hidden size, and experts of which a token takes a few. It says what
its sizes must satisfy and is bound to the serving loop as a shipped
family is; the program has no such model, so ``build`` refuses."""
import functools

from perfbench import serve_loop


def check_widths(c: dict) -> None:
    if c["num_attention_heads"] % c["num_key_value_heads"]:
        raise ValueError(
            f"num_attention_heads {c['num_attention_heads']} is not a "
            f"multiple of num_key_value_heads {c['num_key_value_heads']}")
    if not 0 < c["num_experts_per_tok"] <= c["num_experts"]:
        raise ValueError(
            f"num_experts_per_tok {c['num_experts_per_tok']} of "
            f"num_experts {c['num_experts']}")


def build(ctx):
    raise NotImplementedError("the program serves no such model yet")


def limits(c: dict) -> dict:
    return {"vocab_size": c["vocab_size"], "num_slots": 2, "capacity": 64}


run = functools.partial(serve_loop.run, build=build,
                        warm_up=lambda ctx, engine: None, limits=limits,
                        device_state=lambda engine: engine.pool.pools)
