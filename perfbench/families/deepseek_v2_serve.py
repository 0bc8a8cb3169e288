"""DeepSeek-V2 served by ``ServingEngine``: one chip of the 8 its own router
groups its experts over. The model is ``paddle_tpu.models.deepseek_v2.
DeepseekV2`` (dense latent attention in every layer under YaRN, a softmax
router limited to 3 of 8 groups of experts, a held group of 20, two shared
experts), its sizes from the configuration file under the keys of HF's
``config.json``. A program without that model (the parent of the PR that
brought it) fails at the import in ``model_config``, at once, before any
weight is made.

The model is built under ``paddle.LazyGuard`` and stays abstract: the engine
draws its weights on the chip in one jitted, seeded call, in bf16, straight
into the arrays it serves from (6.3 GB). The engine reads what caches to
keep from the model: latents alone, no indexer keys, no window space.
"""
from __future__ import annotations

import numpy as np

from perfbench import serve_loop

#: every width of the published config.json, by its key: a file that
#: changes one is refused by that key's name
PUBLISHED = {
    "hidden_size": 5120, "intermediate_size": 12288,
    "moe_intermediate_size": 1536, "num_attention_heads": 128,
    "num_key_value_heads": 128, "q_lora_rank": 1536, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts_per_tok": 6, "n_shared_experts": 2, "n_group": 8,
    "topk_group": 3, "first_k_dense_replace": 1, "rope_theta": 10000,
    "rms_norm_eps": 1e-06, "routed_scaling_factor": 16,
    "max_position_embeddings": 163840,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn"}}
#: of the requests an engine serves, one in this many has what its ticks
#: said of it kept for the check (``models/deepseek_v2.TickRecord``)
WATCH_EVERY = 3
#: prompt chunks of a tick (ISSUE 40: 1, 2 or 4). Under ``fifo`` both are
#: the oldest prompt's next two, so two a tick halve the reads of the
#: weights a prompt token costs and mix no phases. It is the engine's
#: policy, which no configuration file holds, so the family passes it.
PREFILL_CHUNKS_PER_TICK = 2


def check_widths(c: dict, published: dict = None) -> None:
    """The file's widths are the published ones (``published``: a toy's own
    table), and the held experts are whole router groups: a whole share of
    the routed experts, from a group's first expert."""
    for key, want in (PUBLISHED if published is None else published).items():
        if c[key] != want:
            raise ValueError(f"{key} {c[key]} is not the published {want}")
    routed = c["published"]["n_routed_experts"]
    group = routed // c["n_group"]
    if routed % c["n_routed_experts"] or c["n_routed_experts"] % group \
            or c["experts_held_first"] % c["n_routed_experts"]:
        raise ValueError(
            f"n_routed_experts {c['n_routed_experts']} held from "
            f"{c['experts_held_first']} is no whole share of the {routed} "
            f"in groups of {group}")
    if c["published"]["vocab_size"] % c["vocab_size"]:
        raise ValueError(f"vocab_size {c['vocab_size']} is no whole share "
                         f"of {c['published']['vocab_size']}")
    if c["num_hidden_layers"] <= c["first_k_dense_replace"]:
        raise ValueError(f"num_hidden_layers {c['num_hidden_layers']} "
                         "holds no expert layer")


def model_config(c: dict, published: dict = None):
    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config

    check_widths(c, published)
    if c["hidden_act"] != "silu" or c["scoring_func"] != "softmax" \
            or c["norm_topk_prob"] or c["attention_bias"] \
            or c["topk_method"] != "group_limited_greedy" \
            or c["tie_word_embeddings"] or c["moe_layer_freq"] != 1:
        raise ValueError(
            "the family runs SiLU-gated FFNs, a softmax router under "
            "group_limited_greedy with unrenormalised weights in every "
            "layer past the dense ones, no biases and an untied head")
    fields = set(DeepseekV2Config.__dataclass_fields__)
    sizes = {k: v for k, v in c.items()
             if k in fields and k != "n_routed_experts"}
    return DeepseekV2Config(
        **sizes, n_routed_experts=c["published"]["n_routed_experts"],
        experts_held=(c["experts_held_first"], c["n_routed_experts"]))


def build(ctx, published: dict = None):
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v2 import DeepseekV2
    from paddle_tpu.serving import ServingConfig, ServingEngine

    c, e = ctx.config, ctx.config["engine"]
    cfg = model_config(c, published)
    if c["dtype"] != "bfloat16":
        raise ValueError(f"dtype {c['dtype']!r}: this family serves bf16")
    paddle.seed(ctx.seed31)
    with paddle.LazyGuard():
        net = DeepseekV2(cfg)
    net.eval()
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(
        num_slots=e["num_slots"], page_size=e["page_size"],
        pages_per_slot=e["pages_per_slot"], kv_dtype=e["kv_dtype"],
        prefix_cache=e["prefix_cache"], decode=e["decode"],
        prefill_chunks_per_tick=PREFILL_CHUNKS_PER_TICK))
    # the check reads what the ticks said of a few requests: one in
    # ``WATCH_EVERY`` is recorded
    eng.tick_record.watch = lambda rid: rid % WATCH_EVERY == 0
    return net, eng


def warm_up(ctx, eng) -> None:
    """The one program the window runs, the tick, run on a prompt of four
    chunks and a half and a few decoded tokens."""
    rng = np.random.default_rng([ctx.seed, 7])
    prompt = rng.integers(0, ctx.config["vocab_size"],
                          4 * eng.prefill_chunk + eng.prefill_chunk // 2,
                          dtype=np.int32)
    eng.submit(prompt, 3)
    eng.run()
    eng.reset_results()


def limits(c: dict) -> dict:
    e = c["engine"]
    return {"vocab_size": c["vocab_size"], "num_slots": e["num_slots"],
            "capacity": e["pages_per_slot"] * e["page_size"]}


def device_state(eng):
    """The page pools, whole."""
    return eng.pool.pools


def facts_after(ctx, eng) -> dict:
    """What the ticks reported of themselves, over the ticks of the whole
    run (warm-in, window and traced stretch), from the program's
    registry."""
    from paddle_tpu.profiler import registry

    reg = registry()

    def count(name):
        return float(reg.counter(name).value)

    told = max(count("serving/tick_stat_ticks"), 1.0)
    stats = {"tick_" + name: count("serving/tick_stat_sum{stat=%s}" % name)
             / told for name in (
                 "group_hit_share", "expert_rows",
                 "expert_load_max_over_mean", "experts_touched_share",
                 "decode_pairs", "decode_keys", "chunk_pairs",
                 "chunk_keys")}
    return stats


def run(ctx, build=build):
    return serve_loop.run(ctx, build=build, warm_up=warm_up, limits=limits,
                          device_state=device_state, facts_after=facts_after)
