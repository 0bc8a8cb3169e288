"""Laguna-S-2.1 served by ``ServingEngine``: stage 0 of a v5litepod-32, one
chip of the four that share each of its six layers. The model is
``paddle_tpu.models.laguna.Laguna`` (full attention of 48 query heads and
windowed attention of 72 over one set of 8 key/value heads, a gate a head; 64
of each sparse layer's 256 experts, a quarter of the vocabulary), its sizes
from the configuration file under the keys of HF's ``config.json``. A program
without that model (the parent of the PR that brought it) fails at the import
in ``model_config``, at once, before any weight is made.

The model is built under ``paddle.LazyGuard`` and stays abstract: the engine
draws its weights on the chip in one jitted, seeded call, in bf16, straight
into the arrays it serves from (7.4 GB). The engine reads what caches to keep
from the model: grouped K/V pages for the full layers and, in a page space
that holds only the window, for the windowed ones.
"""
from __future__ import annotations

from perfbench import loader, serve_loop

#: the warm-up (a prompt of two chunks and a half and a few decoded tokens),
#: the traffic's limits and the arrays an edge of the window waits for (the
#: pools, whole) are those of every served model with a tick of its own: the
#: hybrid's family's
_hybrid = loader.load_module("families", "olmo_hybrid_serve")
warm_up, limits, device_state = (_hybrid.warm_up, _hybrid.limits,
                                 _hybrid.device_state)

_ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
#: every width of the published config.json, by its key (the three cut keys
#: stand under ``published`` in the file): a file that changes one is
#: refused by that key's name
PUBLISHED = {
    "hidden_size": 3072, "intermediate_size": 12288,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "num_experts_per_tok": 10, "moe_routed_scaling_factor": 2.5,
    "sliding_window": 512, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 1048576, "rope_parameters": _ROPE,
    "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 12,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47, "mlp_only_layers": [0],
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12}
#: what the family's forward is written for, by the key that says so
FORMS = {
    "model_type": "laguna", "gating": "per-head", "norm_topk_prob": True,
    "attention_bias": False, "tie_word_embeddings": False,
    "decoder_sparse_step": 1, "moe_apply_router_weight_on_input": False,
    "moe_router_logit_softcapping": 0}
#: what the file may cut, and what the published model has there
CUT = {"num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352}
#: the least of the model a chip keeps (the model-configs guide's floors)
FLOORS = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 12544}
#: of the requests an engine serves, one in this many has what its ticks
#: said of it kept for the check (``models/laguna.TickRecord``)
WATCH_EVERY = 3
#: tokens of a prompt chunk, one a tick: the engine's policy, which no
#: configuration file holds, so the family passes it; a toy passes its own
PREFILL_CHUNK = 256
#: the statistics a tick reports (``models/laguna.TICK_STATS``)
STATS = ("decode_rows", "chunk_tokens", "decode_keys", "chunk_keys",
         "chunk_pairs", "window_decode_keys", "window_chunk_keys",
         "window_chunk_pairs", "expert_rows", "expert_load_max_over_mean",
         "experts_touched_share", "held_rows_unaccounted")


def check_widths(c: dict, published: dict = None, cut: dict = None,
                 floors: dict = None) -> None:
    """The file's widths are the published ones (``published``, ``cut``,
    ``floors``: a toy's own tables), every layer's gate is a head's, the
    router's logits are not capped, and the three keys it may cut say what
    they were cut from and keep the floors; the experts held are the first
    ``num_experts``."""
    for key, want in (PUBLISHED if published is None else published).items():
        if c[key] != want:
            raise ValueError(f"{key} {c[key]} is not the published {want}")
    for key, want in FORMS.items():
        if c[key] != want:
            raise ValueError(f"{key} {c[key]!r}: the family is written for "
                             f"{want!r}")
    if set(c["gating_types"]) != {"per_head"}:
        raise ValueError(f"gating_types {sorted(set(c['gating_types']))}: "
                         "the family serves a gate a head in every layer")
    floors = FLOORS if floors is None else floors
    for key, whole in (CUT if cut is None else cut).items():
        if c["published"][key] != whole:
            raise ValueError(f"published.{key} {c['published'][key]} is not "
                             f"the published {whole}")
        if not floors[key] <= c[key] <= whole:
            raise ValueError(f"{key} {c[key]} is not between the floor "
                             f"{floors[key]} and the published {whole}")
        if (key in c["reduced"]) != (c[key] != whole):
            raise ValueError(f"{key} {c[key]} of {whole}: reduced lists "
                             f"{c['reduced']}")
    if c["experts_held"] != [0, c["num_experts"]]:
        raise ValueError(f"experts_held {c['experts_held']} with "
                         f"num_experts {c['num_experts']}: the first of the "
                         "four shares")


def model_config(c: dict, **tables):
    from paddle_tpu.models.laguna import LagunaConfig

    check_widths(c, **tables)
    fields = set(LagunaConfig.__dataclass_fields__)
    sizes = {k: v for k, v in c.items() if k in fields}
    n = c["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        sizes[key] = tuple(c[key][:n])      # the file keeps them whole
    sizes.update(num_experts=c["published"]["num_experts"],
                 experts_held=tuple(c["experts_held"]))
    return LagunaConfig(**sizes)


def build(ctx, prefill_chunk: int = PREFILL_CHUNK, **tables):
    import paddle_tpu as paddle
    from paddle_tpu.models.laguna import Laguna
    from paddle_tpu.serving import ServingConfig, ServingEngine

    c, e = ctx.config, ctx.config["engine"]
    cfg = model_config(c, **tables)
    if c["dtype"] != "bfloat16":
        raise ValueError(f"dtype {c['dtype']!r}: this family serves bf16")
    paddle.seed(ctx.seed31)
    with paddle.LazyGuard():
        net = Laguna(cfg)
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


def facts_after(ctx, eng) -> dict:
    """What the engine counted and its ticks reported of themselves, means
    over the ticks of the whole run (warm-in, window and traced stretch),
    and what the pools hold, from the program's registry."""
    from paddle_tpu.profiler import registry

    reg = registry()

    def count(name):
        return float(reg.counter(name).value)

    ticks = max(count("serving/ticks"), 1.0)
    told = max(count("serving/tick_stat_ticks"), 1.0)
    facts = {"tick_" + name: count("serving/tick_stat_sum{stat=%s}" % name)
             / told for name in STATS}
    facts["window_pages_freed_per_tick"] = \
        count("serving/window_pages_freed") / ticks
    facts["live_window_share"] = float(
        reg.gauge("serving/live_pages{pool=window}").value)
    facts["attn_paths"] = sorted(path for path in ("pallas", "xla") if count(
        "serving/attn_calls{path=%s}" % path))
    return facts


def run(ctx, build=build):
    return serve_loop.run(ctx, build=build, warm_up=warm_up, limits=limits,
                          device_state=device_state, facts_after=facts_after)
