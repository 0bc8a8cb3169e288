"""Ling-3.0-flash served by ``ServingEngine``: one chip of the four that share
each layer. The model is ``paddle_tpu.models.ling3.Ling3`` (a leading dense
layer and one period of five Kimi Delta Attention layers and a latent
attention layer, 128 of each expert layer's 512 experts, a quarter of the
vocabulary), its sizes from the configuration file under the keys of HF's
``config.json``. A program without that model (the parent of the PR that
brought it) fails at the import in ``model_config``, at once, before any
weight is made.

The model is built under ``paddle.LazyGuard`` and stays abstract: the engine
draws its weights on the chip in one jitted call, in bf16, straight into the
arrays it serves from (10.5 GB), **from one key whatever the run's seed**
(``WEIGHTS_SEED``, which says why: the draw sets the load; the seed makes the
prompts). The engine reads what caches to
keep from the model: a float32 state and a convolution's history a slot for
the KDA layers beside latent rows in pages for the MLA layer, in one pool.

No request of this cell's traffic finishes inside a run (outputs of 9.7 k
and 15.6 k tokens at a tick of over 10 ms): the window is all decode by
design, and ``run`` counts as attempted the requests that were handed tokens
inside it, which ``serve_loop`` counts by the requests that left.
"""
from __future__ import annotations

import numpy as np

from perfbench import serve_loop

#: every width of the published config.json, by its key: a file that
#: changes one is refused by that key's name
PUBLISHED = {
    "hidden_size": 2560, "intermediate_size": 6144,
    "moe_intermediate_size": 768, "moe_shared_expert_intermediate_size": 768,
    "num_attention_heads": 32, "num_key_value_heads": 32, "head_dim": 128,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "kv_lora_rank": 512, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "rope_theta": 6000000, "rope_scaling": None, "layer_group_size": 6,
    "first_k_dense_replace": 2, "num_experts_per_tok": 8,
    "num_shared_experts": 1, "n_group": 8, "topk_group": 4,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-06,
    "max_position_embeddings": 262144}
#: what the family's forward is written for, by the key that says so
FORMS = {
    "hidden_act": "silu", "score_function": "sigmoid",
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "moe_router_enable_expert_bias": True, "kda_safe_gate": True,
    "no_kda_lora": True, "linear_silu": True, "group_norm_size": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "tie_word_embeddings": False, "use_bias": False, "use_qkv_bias": False}
#: the key every run's weights are drawn from; a run's ``--seed`` makes its
#: prompts and turns its traffic's cycle. The other families draw their
#: weights from the run's seed; here **the draw sets the load**: under
#: seeded weights the router's persistent preferences (its columns against
#: what the tokens' hidden states share, and ``expert_bias``: 0.02 in score
#: is 0.2 in the logit of an expert near the top-8's threshold, half again
#: as many rows or a third fewer) decide how many of the 128 held experts a
#: tick touches (55.3-59.4 % over four traced seeds) and the experts are
#: two thirds of the tick, so twelve seeds read 3,166-3,358 tokens/s,
#: quartile spreads of 3.0 and 2.5 % a set of six where 1.5 % admits a cell,
#: every slice of every run steady (my chip runs, PR 49; a bias drawn as
#: each group's own quantiles, so that no seed moves a group's load, left
#: 2.5 %). ``perfbench/draws.py``'s rule for traffic, that seeds differ in
#: content and never in the amount of work, applied to the weights
WEIGHTS_SEED = 20261002
#: of the requests an engine serves, one in this many has what its ticks
#: said of it kept for the check (``models/ling3.TickRecord``)
WATCH_EVERY = 8
#: tokens of a prompt chunk, one a tick (ISSUE 49; the pool of a state
#: refuses two). It is the engine's policy, which no configuration file
#: holds, so the family passes it; a toy passes its own
PREFILL_CHUNK = 256
#: the statistics a tick reports (``models/ling3.TICK_STATS``)
STATS = ("live_state_rows", "chunk_tokens", "decode_keys", "chunk_keys",
         "decode_pairs", "chunk_pairs", "group_hit_share", "expert_rows",
         "expert_load_max_over_mean", "experts_touched_share",
         "held_rows_unaccounted")


def check_widths(c: dict, published: dict = None) -> None:
    """The file's widths are the published ones (``published``: a toy's own
    table); the layers held are the leading dense layer and whole periods
    after it, in their published order, and none of them clamps its SwiGLU
    (the clamp's equation is not public); the experts held are whole groups
    of the router's."""
    for key, want in (PUBLISHED if published is None else published).items():
        if c[key] != want:
            raise ValueError(f"{key} {c[key]} is not the published {want}")
    for key, want in FORMS.items():
        if c[key] != want:
            raise ValueError(f"{key} {c[key]!r}: the family is written for "
                             f"{want!r}")
    held, period = c["layers_held"], c["layer_group_size"]
    dense = c["first_k_dense_replace"]
    if len(held) != c["num_hidden_layers"] or held[0] >= dense \
            or held[1:] != list(range(dense, dense + len(held) - 1)) \
            or (len(held) - 1) % period:
        raise ValueError(
            f"layers_held {held} for num_hidden_layers "
            f"{c['num_hidden_layers']}: one leading dense layer and then "
            f"whole periods of {period} from layer {dense} on")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(c[key][i] for i in held):
            raise ValueError(f"{key} is not 0 in a layer held: the clamp's "
                             "equation is not public and is not guessed")
    first, count = c["experts_held"]
    per_group = c["published"]["num_experts"] // c["n_group"]
    if count != c["num_experts"] or first % per_group or count % per_group:
        raise ValueError(
            f"experts_held {c['experts_held']} with num_experts "
            f"{c['num_experts']}: whole groups of {per_group}")


def model_config(c: dict, published: dict = None):
    from paddle_tpu.models.ling3 import Ling3Config

    check_widths(c, published)
    fields = set(Ling3Config.__dataclass_fields__)
    sizes = {k: v for k, v in c.items() if k in fields}
    sizes.update(num_experts=c["published"]["num_experts"],
                 layer_ids=tuple(c["layers_held"]),
                 experts_held=tuple(c["experts_held"]))
    return Ling3Config(**sizes)


def build(ctx, published: dict = None, prefill_chunk: int = PREFILL_CHUNK):
    import paddle_tpu as paddle
    from paddle_tpu.models.ling3 import Ling3
    from paddle_tpu.serving import ServingConfig, ServingEngine

    c, e = ctx.config, ctx.config["engine"]
    cfg = model_config(c, published)
    if c["dtype"] != "bfloat16":
        raise ValueError(f"dtype {c['dtype']!r}: this family serves bf16")
    paddle.seed(WEIGHTS_SEED)
    with paddle.LazyGuard():
        net = Ling3(cfg)
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
    chunks and a half and a few decoded tokens. What the engine's log of
    events holds from here on is the run's own (``facts_after``)."""
    from paddle_tpu.profiler import events

    rng = np.random.default_rng([ctx.seed, 7])
    prompt = rng.integers(0, ctx.config["vocab_size"],
                          2 * eng.prefill_chunk + eng.prefill_chunk // 2,
                          dtype=np.int32)
    eng.submit(prompt, 3)
    eng.run()
    eng.reset_results()
    ctx.events_from = events.log().next_seq


def limits(c: dict) -> dict:
    e = c["engine"]
    return {"vocab_size": c["vocab_size"], "num_slots": e["num_slots"],
            "capacity": e["pages_per_slot"] * e["page_size"]}


def device_state(eng):
    """The pools, whole: latent pages, states and histories."""
    return eng.pool.pools


def warm_prefill(ctx, slots: int) -> dict:
    """How fast the first wave's prompts became resident, from the engine's
    own log of events: the prompt tokens of the chunks dispatched up to the
    ``slots``-th request's first token, over the time from the first chunk
    to that token. The chunk path's only reading in a cell whose window is
    all decode; not judged."""
    from paddle_tpu.profiler import events

    evs, _ = events.log().since(getattr(ctx, "events_from", 0))
    first = [ev for ev in evs if ev.kind == "first_token"][:slots]
    chunks = [ev for ev in evs if ev.kind == "chunk"]
    if len(first) < slots or not chunks:
        return {}
    t0, t1 = chunks[0].t_ns, first[-1].t_ns
    tokens = sum(ev.attrs["end"] - ev.attrs["start"] for ev in chunks
                 if ev.t_ns <= t1)
    took = max(t1 - t0, 1) / 1e9
    return {"warm_prefill_tokens": float(tokens), "warm_prefill_s": took,
            "warm_prefill_tokens_per_s": tokens / took}


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
    facts["live_latent_share"] = float(
        reg.gauge("serving/live_pages{pool=latent}").value)
    facts["gdn_paths"] = {
        kind: sorted(path for path in ("pallas", "xla") if count(
            "gdn/%s_calls{path=%s}" % (kind, path)))
        for kind in ("step", "chunk", "prep")}
    facts.update(warm_prefill(ctx, ctx.config["engine"]["num_slots"]))
    return facts


def run(ctx, build=build):
    out = serve_loop.run(ctx, build=build, warm_up=warm_up, limits=limits,
                         device_state=device_state, facts_after=facts_after)
    if not out["attempted"]:
        # no request left the engine inside the window: the operations are
        # the requests it decoded for (every one still has tokens to come)
        out["attempted"] = int(round(out["facts"]["decode_rows_per_tick"]))
    return out
