"""The language model of dots3-note-prev served by ``ServingEngine``: one
chip's share of a layer that 8 chips serve together. The model is
``paddle_tpu.models.dots3.Dots3`` (latent attention with a sparse indexer
in the full layers, windowed latent attention in the sliding layers, a held
share of a sigmoid-routed mixture), its sizes from the configuration file
under the keys of HF's ``config.json``. A program without that model (the
parent of the PR that brought it) fails at the import in ``model_config``,
at once, before any weight is made.

The model is built under ``paddle.LazyGuard`` and stays abstract: the engine
draws its weights on the chip in one jitted, seeded call, in bf16, straight
into the stacks it serves from (8.2 GB; a float32 model beside them would
not fit). The engine reads what caches to keep from the model.
"""
from __future__ import annotations

import numpy as np

from perfbench import serve_loop

#: every width of the published config.json, by its key: a file that
#: changes one is refused by that key's name
PUBLISHED = {
    "hidden_size": 5120, "intermediate_size": 13824,
    "moe_intermediate_size": 1536, "num_attention_heads": 128,
    "num_key_value_heads": 128, "q_lora_rank": 1024, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64,
    "swa_q_lora_rank": 1024, "swa_kv_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_v_head_dim": 128, "sliding_window_size": 513,
    "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
    "num_experts_per_tok": 8, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "rope_theta": 80000000,
    "swa_rope_theta": 50000, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 1}
#: of the requests an engine serves, one in this many has what its ticks
#: said of it kept for the check (``models/dots3.TickRecord``)
WATCH_EVERY = 3


def check_widths(c: dict, published: dict = None) -> None:
    """The file's widths are the published ones (``published``: a toy's own
    table), its layer list names every layer, full layers come first, and
    the held experts are a whole share of the router's."""
    for key, want in (PUBLISHED if published is None else published).items():
        if c[key] != want:
            raise ValueError(f"{key} {c[key]} is not the published {want}")
    if len(c["layer_types"]) != c["num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(c['layer_types'])} layers, "
            f"num_hidden_layers {c['num_hidden_layers']}")
    routed = c["published"]["n_routed_experts"]
    if routed % c["n_routed_experts"] or \
            c["experts_held_first"] % c["n_routed_experts"]:
        raise ValueError(
            f"n_routed_experts {c['n_routed_experts']} held from "
            f"{c['experts_held_first']} is no whole share of the {routed}")
    if c["published"]["vocab_size"] % c["vocab_size"]:
        raise ValueError(f"vocab_size {c['vocab_size']} is no whole share "
                         f"of {c['published']['vocab_size']}")


def model_config(c: dict, published: dict = None):
    from paddle_tpu.models.dots3 import Dots3Config

    check_widths(c, published)
    if c["hidden_act"] != "silu" or c["rope_scaling"] is not None \
            or c["scoring_func"] != "sigmoid" or not c["norm_topk_prob"] \
            or c["topk_method"] != "noaux_tc" or c["attention_bias"] \
            or c["tie_word_embeddings"] or c["moe_layer_freq"] != 1 \
            or {c["attention_gate_type"],
                c["swa_attention_gate_type"]} != {"headwise"}:
        raise ValueError(
            "the family runs SiLU-gated FFNs, unscaled RoPE, a sigmoid "
            "router with a selection bias and renormalised weights in every "
            "layer past the dense ones, headwise gates, no biases and an "
            "untied head")
    fields = set(Dots3Config.__dataclass_fields__)
    sizes = {k: v for k, v in c.items()
             if k in fields and k not in ("layer_types", "n_routed_experts")}
    return Dots3Config(
        **sizes, layer_types=tuple(c["layer_types"]),
        n_routed_experts=c["published"]["n_routed_experts"],
        experts_held=(c["experts_held_first"], c["n_routed_experts"]))


def build(ctx, published: dict = None):
    import paddle_tpu as paddle
    from paddle_tpu.models.dots3 import Dots3
    from paddle_tpu.serving import ServingConfig, ServingEngine

    c, e = ctx.config, ctx.config["engine"]
    cfg = model_config(c, published)
    if c["dtype"] != "bfloat16":
        raise ValueError(f"dtype {c['dtype']!r}: this family serves bf16")
    paddle.seed(ctx.seed31)
    with paddle.LazyGuard():
        net = Dots3(cfg)
    net.eval()
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(
        num_slots=e["num_slots"], page_size=e["page_size"],
        pages_per_slot=e["pages_per_slot"], kv_dtype=e["kv_dtype"],
        prefix_cache=e["prefix_cache"], decode=e["decode"]))
    # the check reads what the ticks said of a few requests: one in
    # ``WATCH_EVERY`` is recorded
    eng.tick_record.watch = lambda rid: rid % WATCH_EVERY == 0
    return net, eng


def warm_up(ctx, eng) -> None:
    """The one program the window runs, the tick, run on a prompt of two
    chunks and a half and a few decoded tokens."""
    rng = np.random.default_rng([ctx.seed, 7])
    prompt = rng.integers(0, ctx.config["vocab_size"],
                          2 * eng.prefill_chunk + eng.prefill_chunk // 2,
                          dtype=np.int32)
    eng.submit(prompt, 3)
    eng.run()
    eng.reset_results()


def limits(c: dict) -> dict:
    e = c["engine"]
    return {"vocab_size": c["vocab_size"], "num_slots": e["num_slots"],
            "capacity": e["pages_per_slot"] * e["page_size"]}


def device_state(eng):
    """The page pools, whole: latents, indexer keys and the window's."""
    return eng.pool.pools


def facts_after(ctx, eng) -> dict:
    """What the engine counted and its ticks reported of themselves, over
    the ticks of the whole run (warm-in, window and traced stretch), from
    the program's registry; the pools' shapes for the yardstick."""
    from paddle_tpu.profiler import registry

    reg = registry()

    def count(name):
        return float(reg.counter(name).value)

    ticks = max(count("serving/ticks"), 1.0)
    told = max(count("serving/tick_stat_ticks"), 1.0)
    stats = {"tick_" + name: count("serving/tick_stat_sum{stat=%s}" % name)
             / told for name in (
                 "selected_share", "expert_rows",
                 "expert_load_max_over_mean", "experts_touched_share")}
    pools = eng.pool.pools
    return {"window_pages_freed_per_tick":
            count("serving/window_pages_freed") / ticks,
            "live_window_share":
            float(reg.gauge("serving/live_pages{pool=window}").value),
            "latent_dims": tuple(pools.latent.shape),
            "window_dims": tuple(pools.window.shape), **stats}


def run(ctx, build=build):
    return serve_loop.run(ctx, build=build, warm_up=warm_up, limits=limits,
                          device_state=device_state, facts_after=facts_after)
