"""Olmo-Hybrid-7B served by ``ServingEngine``: one chip of the two that hold
it. The model is ``paddle_tpu.models.olmo_hybrid.OlmoHybrid`` (three Gated
DeltaNet layers and then a full-attention layer, four such periods here),
its sizes from the configuration file under the keys of HF's
``config.json``. A program without that model (the parent of the PR that
brought it) fails at the import in ``model_config``, at once, before any
weight is made.

The model is built under ``paddle.LazyGuard`` and stays abstract: the engine
draws its weights on the chip in one jitted, seeded call, in bf16, straight
into the arrays it serves from (8.2 GB). The engine reads what caches to
keep from the model: K/V pages for the full layers, a float32 state and a
convolution's history a slot for the linear ones.
"""
from __future__ import annotations

import numpy as np

from perfbench import serve_loop

#: every width of the published config.json, by its key: a file that
#: changes one is refused by that key's name
PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rms_norm_eps": 1e-06, "max_position_embeddings": 65536,
    "rope_parameters": {"rope_theta": None}}
#: of the requests an engine serves, one in this many has what its ticks
#: said of it kept for the check (``models/olmo_hybrid.TickRecord``)
WATCH_EVERY = 3
#: tokens of a prompt chunk, one a tick (ISSUE 44). It is the engine's
#: policy, which no configuration file holds, so the family passes it; a toy
#: passes its own
PREFILL_CHUNK = 256
#: the statistics a tick reports (``models/olmo_hybrid.TICK_STATS``)
STATS = ("live_state_rows", "chunk_tokens", "decode_keys", "chunk_keys",
         "chunk_pairs")


def check_widths(c: dict, published: dict = None) -> None:
    """The file's widths are the published ones (``published``: a toy's own
    table), and the layers kept are whole periods from the first."""
    for key, want in (PUBLISHED if published is None else published).items():
        if c[key] != want:
            raise ValueError(f"{key} {c[key]} is not the published {want}")
    kinds = c["layer_types"]
    if len(kinds) != c["published"]["num_hidden_layers"]:
        raise ValueError(f"layer_types lists {len(kinds)} layers, not the "
                         f"published {c['published']['num_hidden_layers']}")
    period = kinds.index("full_attention") + 1
    if c["num_hidden_layers"] % period or kinds != (
            ["linear_attention"] * (period - 1) + ["full_attention"]) \
            * (len(kinds) // period):
        raise ValueError(
            f"num_hidden_layers {c['num_hidden_layers']} is not whole "
            f"periods of {period} layers of layer_types")


def model_config(c: dict, published: dict = None):
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig

    check_widths(c, published)
    if c["hidden_act"] != "silu" or c["attention_bias"] \
            or c["tie_word_embeddings"]:
        raise ValueError("the family runs SiLU-gated FFNs, no biases and "
                         "an untied head")
    fields = set(OlmoHybridConfig.__dataclass_fields__)
    sizes = {k: v for k, v in c.items() if k in fields}
    sizes["layer_types"] = tuple(c["layer_types"])
    return OlmoHybridConfig(**sizes)


def build(ctx, published: dict = None, prefill_chunk: int = PREFILL_CHUNK):
    import paddle_tpu as paddle
    from paddle_tpu.models.olmo_hybrid import OlmoHybrid
    from paddle_tpu.serving import ServingConfig, ServingEngine

    c, e = ctx.config, ctx.config["engine"]
    cfg = model_config(c, published)
    if c["dtype"] != "bfloat16":
        raise ValueError(f"dtype {c['dtype']!r}: this family serves bf16")
    paddle.seed(ctx.seed31)
    with paddle.LazyGuard():
        net = OlmoHybrid(cfg)
    net.eval()
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(
        num_slots=e["num_slots"], page_size=e["page_size"],
        pages_per_slot=e["pages_per_slot"],
        prefill_chunk=prefill_chunk, kv_dtype=e["kv_dtype"],
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
    """The pools, whole: K/V pages, states and histories."""
    return eng.pool.pools


def facts_after(ctx, eng) -> dict:
    """What the ticks reported of themselves, means over the ticks of the
    whole run (warm-in, window and traced stretch), and what the pool holds,
    from the program's registry."""
    from paddle_tpu.profiler import registry

    reg = registry()

    def count(name):
        return float(reg.counter(name).value)

    told = max(count("serving/tick_stat_ticks"), 1.0)
    facts = {"tick_" + name: count("serving/tick_stat_sum{stat=%s}" % name)
             / told for name in STATS}
    facts["state_bytes"] = float(reg.gauge("serving/state_bytes").value)
    facts["live_state_share"] = float(
        reg.gauge("serving/live_pages{pool=state}").value)
    facts["gdn_paths"] = {
        kind: sorted(path for path in ("pallas", "xla") if count(
            "gdn/%s_calls{path=%s}" % (kind, path)))
        for kind in ("step", "chunk")}
    return facts


def run(ctx, build=build):
    return serve_loop.run(ctx, build=build, warm_up=warm_up, limits=limits,
                          device_state=device_state, facts_after=facts_after)
