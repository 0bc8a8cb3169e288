"""Falcon-H1-34B-Instruct served by ``ServingEngine``: one pipeline stage of
nine layers of the eight that hold it. The model is
``paddle_tpu.models.falcon_h1.FalconH1`` (in every block a Mamba-2 mixer and
grouped-query attention side by side, then a SwiGLU), its sizes and
multipliers from the configuration file under the keys of HF's
``config.json``. A program without that model (the parent of the PR that
brought it) fails at the import in ``model_config``, at once, before any
weight is made.

The model is built under ``paddle.LazyGuard`` and stays abstract: the engine
draws its weights on the chip in one jitted, seeded call, in bf16, straight
into the arrays it serves from (8.4 GB). The engine reads what caches to keep
from the model: in every layer K/V pages of 4 heads under 20 query heads and
a float32 state with a convolution's history a slot.
"""
from __future__ import annotations

from perfbench import loader, serve_loop

#: the warm-up (a prompt of two chunks and a half and a few decoded tokens),
#: the traffic's limits and the arrays an edge of the window waits for (the
#: pools, whole) are those of every served model with a state a slot: the
#: hybrid's family's, as the checks share the dots3 check's sample
_hybrid = loader.load_module("families", "olmo_hybrid_serve")
warm_up, limits, device_state = (_hybrid.warm_up, _hybrid.limits,
                                 _hybrid.device_state)

#: every key of the published config.json that shapes the model, the
#: multipliers among them (the two cut keys stand under ``published`` in the
#: file): a file that changes one is refused by that key's name
PUBLISHED = {
    "hidden_size": 5120, "intermediate_size": 21504,
    "num_attention_heads": 20, "num_key_value_heads": 4, "head_dim": 128,
    "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128,
    "mamba_d_state": 256, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 128, "mamba_expand": 2, "mlp_expansion_factor": 8,
    "mamba_conv_bias": True, "mamba_norm_before_gate": False,
    "mamba_rms_norm": True, "mamba_proj_bias": False, "mamba_use_mlp": True,
    "rope_theta": 100000000000, "rope_scaling": None, "rms_norm_eps": 1e-05,
    "max_position_embeddings": 262144,
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "ssm_in_multiplier": 0.25,
    "ssm_out_multiplier": 0.08838834764831845,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "key_multiplier": 0.011048543456039804,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284]}
#: what the file may cut, and what the published model has there
CUT = {"num_hidden_layers": 72, "vocab_size": 261120}
#: the least of the model a stage keeps (the model-configs guide's floors:
#: four layers, an eighth of the vocabulary)
FLOORS = {"num_hidden_layers": 4, "vocab_size": 261120 // 8}
#: of the requests an engine serves, one in this many has what its ticks
#: said of it kept for the check; tokens of a prompt chunk, one a tick (the
#: engine's policy, which no configuration file holds, so the family passes
#: it; a toy passes its own); the statistics a tick reports
#: (``models/falcon_h1.TICK_STATS`` are the hybrid's)
WATCH_EVERY, PREFILL_CHUNK, STATS = (
    _hybrid.WATCH_EVERY, _hybrid.PREFILL_CHUNK, _hybrid.STATS)


def check_widths(c: dict, published: dict = None, cut: dict = None,
                 floors: dict = None) -> None:
    """The file's widths and multipliers are the published ones
    (``published``, ``cut``, ``floors``: a toy's own tables), and the two
    keys it may cut say what they were cut from and keep the floors."""
    for key, want in (PUBLISHED if published is None else published).items():
        if c[key] != want:
            raise ValueError(f"{key} {c[key]} is not the published {want}")
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


def model_config(c: dict, **tables):
    from paddle_tpu.models.falcon_h1 import FalconH1Config

    check_widths(c, **tables)
    if c["hidden_act"] != "silu" or c["attention_bias"] or c["mlp_bias"] \
            or c["projectors_bias"] or c["tie_word_embeddings"] \
            or c["attn_layer_indices"] is not None:
        raise ValueError("the family runs SiLU-gated FFNs, no biases but "
                         "the convolution's, attention in every layer and "
                         "an untied head")
    fields = set(FalconH1Config.__dataclass_fields__)
    sizes = {k: v for k, v in c.items() if k in fields}
    for key in ("ssm_multipliers", "mlp_multipliers"):
        sizes[key] = tuple(c[key])
    return FalconH1Config(**sizes)


def build(ctx, prefill_chunk: int = PREFILL_CHUNK, **tables):
    import paddle_tpu as paddle
    from paddle_tpu.models.falcon_h1 import FalconH1
    from paddle_tpu.serving import ServingConfig, ServingEngine

    c, e = ctx.config, ctx.config["engine"]
    cfg = model_config(c, **tables)
    if c["dtype"] != "bfloat16":
        raise ValueError(f"dtype {c['dtype']!r}: this family serves bf16")
    paddle.seed(ctx.seed31)
    with paddle.LazyGuard():
        net = FalconH1(cfg)
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
    facts["ssd_paths"] = {
        kind: sorted(path for path in ("pallas", "xla") if count(
            "ssd/%s_calls{path=%s}" % (kind, path)))
        for kind in ("step", "chunk", "prep")}
    return facts


def run(ctx, build=build):
    return serve_loop.run(ctx, build=build, warm_up=warm_up, limits=limits,
                          device_state=device_state, facts_after=facts_after)
