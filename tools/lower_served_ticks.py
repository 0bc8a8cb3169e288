"""The lowered serving ticks of the served configurations, for a TPU v5e,
without the chip: the proof that a refactor of the engine <-> model <-> cache
seam left every cell's program as it was (ISSUE 43).

    python tools/lower_served_ticks.py <out_dir>            # in each tree
    python tools/lower_served_ticks.py --compare <a> <b>    # then

Run the first form once from the root of the parent's tree and once from the
change's (``git archive <commit> | tar -x -C <dir>``), with ``JAX_PLATFORMS=cpu``:
an engine is built and ticked on the CPU at the rows of ``gpt3-1.3b-serve`` and
``ouro-2.6b-serve``, at a tiny width with a draft model (both ticks), for a
dots3 and a DeepSeek-V2 model at the published head counts and latent widths,
and for an Olmo-Hybrid (PR 44), a Ling-3.0 (PR 49), a Falcon-H1 (PR 54) and
a Laguna model (PR 57) at the published head sizes;
each tick is lowered again from the avals of its first dispatch as a program
traced for the TPU (the attention kernels inside), and its StableHLO text,
which carries no locations, is written to ``<out_dir>/<name>.<site>.txt``,
and beside it ``.scopes``: how many operations carry each name stack (the
scopes a device trace charges them to, which the text does not show).
The second form compares two such directories: the text outside the Mosaic
kernels' serialized bodies, which must be equal, and the bodies, which carry
their operations' locations and so differ by the tree's root path: they are
compared after that path (keep the innermost frame alone, as here, or the
call stack's line numbers are in them too). A body that still differs, as
every kernel below an edited line of its file does, is parsed and compared
as text without its locations (PR 57): ``equal but for locations`` is the
same program, ``DIFFER`` is not. Where the two sides' bodies name other
source files, the line says which: since PR 60 the latent kernels
(``latent_attn``, ``selected_latent_attn``) are traced from
``ops/latent_attention.py``, the ragged and grouped ones and the walk all
three share (``_walk_pages``) from ``ops/paged_attention.py``, so against a
tree from before it every latent body reads ``equal but for locations``.
Nothing is run on a chip and no number comes out of this.
"""
import base64
import os
import re
import sys
from collections import Counter

_BODY = re.compile(r'body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def _without_locations(bodies):
    """Each serialized Mosaic body as MLIR text with no debug information."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True      # ``stable_mosaic.*``
    with ctx:
        return [ir.Module.parse(k).operation.get_asm(enable_debug_info=False)
                for k in bodies]


def _sources(bodies):
    """The repo's files that the bodies' locations name."""
    return {f.decode() for k in bodies
            for f in re.findall(rb"paddle_tpu/[\w/]+\.py", k)}


def compare(a: str, b: str) -> int:
    roots, worst = [], 0
    for name in sorted(os.listdir(a)):
        ta, tb = (open(os.path.join(d, name)).read() for d in (a, b))
        if name.endswith(".scopes"):
            moved = sorted(set(ta.splitlines()) ^ set(tb.splitlines()))
            print(f"{name}: {'equal' if not moved else 'DIFFERS'}")
            print("".join(f"  {m}\n" for m in moved[:20]), end="")
            worst |= bool(moved)
            continue
        same_text = _BODY.sub("body", ta) == _BODY.sub("body", tb)
        ka, kb = ([base64.b64decode(x) for x in _BODY.findall(t)]
                  for t in (ta, tb))
        if not roots:
            roots = [re.search(rb"(/[ -~]*?)/paddle_tpu/", k[0]).group(1)
                     if k else b"" for k in (ka, kb)]
        same_kernels = len(ka) == len(kb) and all(
            x.replace(roots[0], roots[1]) == y for x, y in zip(ka, kb))
        said = "equal after the root path" if same_kernels else "DIFFER"
        if not same_kernels and len(ka) == len(kb):
            pairs = list(zip(_without_locations(ka), _without_locations(kb)))
            moved = [re.search(r"module @(\w+)", x).group(1)
                     for x, y in pairs if x != y]
            same_kernels = not moved
            said = "equal but for locations" if same_kernels \
                else f"DIFFER ({', '.join(moved)})"
            one_side = sorted(_sources(ka) ^ _sources(kb))
            if one_side:
                said += f" [one side alone names {', '.join(one_side)}]"
        print(f"{name}: text outside kernels "
              f"{'equal' if same_text else 'DIFFERS'}, {len(ka)} kernels "
              f"{said}")
        for la, lb in zip(_BODY.sub("body", ta).splitlines(),
                          _BODY.sub("body", tb).splitlines()):
            if la != lb:        # where a differing line first parts
                at = next(i for i, (x, y) in enumerate(zip(la, lb)) if x != y)
                print(f"  < {la[max(at - 60, 0):at + 60]}\n"
                      f"  > {lb[max(at - 60, 0):at + 60]}")
        worst |= not (same_text and same_kernels)
    return worst


if sys.argv[1] == "--compare":
    sys.exit(compare(sys.argv[2], sys.argv[3]))

sys.path.insert(0, os.getcwd())
out_dir = sys.argv[1]
os.makedirs(out_dir, exist_ok=True)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# a Mosaic kernel's serialized body carries its ops' locations: keep the
# innermost frame alone (the kernel's own file: ops/paged_attention.py or,
# for the latent kernels since PR 60, ops/latent_attention.py)
jax.config.update("jax_traceback_in_locations_limit", 1)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.models import GPT, GPTConfig  # noqa: E402
from paddle_tpu.serving import ServingConfig, ServingEngine  # noqa: E402

dev = topologies.get_topology_desc(platform="tpu",
                                   topology_name="v5e:2x4").devices[0]


def lower(name, eng):
    for i, site in enumerate(eng.compiled_sites):
        fn, avals = eng._program_args[site]
        avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=SingleDeviceSharding(dev)), avals)
        os.environ["PADDLE_TPU_TARGET_PLATFORM"] = "tpu"
        jax.clear_caches()
        lowered = fn.lower(*avals)
        text = lowered.as_text()
        # every operation's name stack (the scopes a trace charges it to),
        # counted: the text above carries none
        debug = lowered.as_text(debug_info=True)
        named = dict(re.findall(r'(#loc\d+) = loc\("(jit\([^"]+)"', debug))
        scopes = Counter(named[ref] for ref in re.findall(
            r'loc\((#loc\d+)\)$', debug, re.M) if ref in named)
        with open(os.path.join(out_dir, f"{name}.{i}.scopes"), "w") as f:
            f.writelines(f"{n} {k}\n" for k, n in sorted(scopes.items()))
        os.environ.pop("PADDLE_TPU_TARGET_PLATFORM")
        path = os.path.join(out_dir, f"{name}.{i}.txt")
        with open(path, "w") as f:
            f.write(text)
        print(name, i, len(text), "tpu_custom_call" in text, flush=True)


def gpt(cfg, serving, **spec):
    paddle.seed(0)
    net = GPT(cfg)
    net.eval()
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(**serving, **spec))
    eng.submit(np.arange(5, dtype=np.int32), 3)
    for _ in range(3):
        eng.step()
    eng.drain(0)
    return eng


lower("gpt3-rows", gpt(
    GPTConfig(vocab_size=256, num_layers=2, hidden_size=2048, num_heads=16,
              max_seq_len=2048, ffn_hidden_size=256),
    dict(num_slots=12, page_size=16)))
lower("ouro-rows", gpt(
    GPTConfig(vocab_size=256, num_layers=2, ffn_hidden_size=384,
              layer_norm_eps=1e-6, tie_word_embeddings=False, norm="rmsnorm",
              position="rope", rope_theta=1e6, bias=False, ffn="swiglu",
              sandwich_norm=True, loop_steps=4, hidden_size=2048,
              num_heads=16, max_seq_len=512),
    dict(num_slots=10, page_size=16)))

# a spec-decoding engine at a tiny width: verify tick and draft tick
from paddle_tpu.serving import SpecConfig  # noqa: E402

paddle.seed(1)
draft = GPT(GPTConfig(vocab_size=256, num_layers=1, hidden_size=256,
                      num_heads=2, max_seq_len=128))
draft.eval()
draft.bfloat16()
lower("spec-tiny", gpt(
    GPTConfig(vocab_size=256, num_layers=2, hidden_size=256, num_heads=2,
              max_seq_len=128),
    dict(num_slots=2, page_size=16), spec=SpecConfig(draft_model=draft, k=3)))

from paddle_tpu.models.deepseek_v2 import DeepseekV2, DeepseekV2Config  # noqa
from paddle_tpu.models.dots3 import FULL, SLIDING, Dots3, Dots3Config  # noqa


def latent(net, **kw):
    net.bfloat16()
    eng = ServingEngine(net, ServingConfig(
        num_slots=4, page_size=128, pages_per_slot=24, prefill_chunk=256,
        prefix_cache=False, **kw))
    eng.submit(np.arange(300, dtype=np.int32) % 512, 2)
    for _ in range(3):
        eng.step()
    eng.drain(0)
    return eng


paddle.seed(0)
with paddle.LazyGuard():
    d3 = Dots3(Dots3Config(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=3,
        layer_types=(FULL, FULL, SLIDING), n_routed_experts=16,
        experts_held=(0, 2), q_lora_rank=128, swa_q_lora_rank=128))
lower("dots3", latent(d3))
with paddle.LazyGuard():
    v2 = DeepseekV2(DeepseekV2Config(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=2, n_routed_experts=16,
        experts_held=(0, 2), q_lora_rank=128))
lower("dsv2", latent(v2, prefill_chunks_per_tick=2))

# a hybrid of linear-attention and full layers at its published head sizes
# (4 of each kind's heads over a small hidden size): K/V pages beside a
# state a slot. A tree without the model (before PR 44) writes no file for it.
try:
    from paddle_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
except ImportError:
    OlmoHybrid = None
if OlmoHybrid is not None:
    paddle.seed(0)
    with paddle.LazyGuard():
        hybrid = OlmoHybrid(OlmoHybridConfig(
            vocab_size=512, hidden_size=512, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=4, linear_num_key_heads=4,
            linear_num_value_heads=4, max_position_embeddings=2048))
    hybrid.bfloat16()
    eng = ServingEngine(hybrid, ServingConfig(
        num_slots=4, page_size=16, pages_per_slot=88, prefill_chunk=256,
        prefix_cache=False))
    eng.submit(np.arange(300, dtype=np.int32) % 512, 2)
    for _ in range(3):
        eng.step()
    eng.drain(0)
    lower("olmoh", eng)

# Ling-3.0-flash at its published head sizes (4 KDA heads of 128 x 128, a
# decay a channel, beside 16 latent heads over rows of 128 + 64; 16 experts
# in 4 groups under the sigmoid group limit): a state a slot beside latent
# pages in one pool. A tree without the model (before PR 49) writes no file.
try:
    from paddle_tpu.models.ling3 import Ling3, Ling3Config
except ImportError:
    Ling3 = None
if Ling3 is not None:
    paddle.seed(0)
    with paddle.LazyGuard():
        ling = Ling3(Ling3Config(
            vocab_size=512, hidden_size=512, intermediate_size=512,
            moe_intermediate_size=128,
            moe_shared_expert_intermediate_size=128, num_hidden_layers=4,
            layer_ids=(1, 3, 4, 5), num_attention_heads=16,
            kv_lora_rank=128, num_experts=16, n_group=4, topk_group=2,
            num_experts_per_tok=4, experts_held=(4, 8),
            select_bias_range=0.02, max_position_embeddings=2048))
    ling.bfloat16()
    eng = ServingEngine(ling, ServingConfig(
        num_slots=8, page_size=128, pages_per_slot=8, prefill_chunk=256,
        prefix_cache=False))
    eng.submit(np.arange(300, dtype=np.int32) % 512, 2)
    for _ in range(3):
        eng.step()
    eng.drain(0)
    lower("ling3", eng)

# Falcon-H1 at its published head sizes (8 SSD heads of 128 channels and a
# state of 256 in 2 groups, a convolution over 2,048 channels; 20 query heads
# over 4 key/value heads of 128): a state a slot AND grouped K/V pages in
# every layer. A tree without the model (before PR 54) writes no file.
try:
    from paddle_tpu.models.falcon_h1 import FalconH1, FalconH1Config
except ImportError:
    FalconH1 = None
if FalconH1 is not None:
    paddle.seed(0)
    with paddle.LazyGuard():
        falcon = FalconH1(FalconH1Config(
            vocab_size=512, hidden_size=512, intermediate_size=512,
            num_hidden_layers=2, mamba_d_ssm=1024, mamba_n_heads=8,
            max_position_embeddings=2048))
    falcon.bfloat16()
    eng = ServingEngine(falcon, ServingConfig(
        num_slots=8, page_size=16, pages_per_slot=88, prefill_chunk=256,
        prefix_cache=False))
    eng.submit(np.arange(300, dtype=np.int32) % 512, 2)
    for _ in range(3):
        eng.step()
    eng.drain(0)
    lower("falcon-h1", eng)

# Laguna at its published head size (12 and 18 query heads over 2 key/value
# heads of 128: six and nine a key/value head; a window of 64 over pages of
# 16; 8 of 16 experts held): full and windowed grouped K/V pages, the
# windowed layers' in a page space of their own. A tree without the model
# (before PR 57) writes no file.
try:
    from paddle_tpu.models.laguna import Laguna, LagunaConfig
except ImportError:
    Laguna = None
if Laguna is not None:
    paddle.seed(0)
    with paddle.LazyGuard():
        laguna = Laguna(LagunaConfig(
            vocab_size=512, hidden_size=512, intermediate_size=512,
            num_hidden_layers=3, num_attention_heads=12,
            num_key_value_heads=2,
            layer_types=("full_attention",) + ("sliding_attention",) * 2,
            num_attention_heads_per_layer=(12, 18, 18), num_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=128,
            shared_expert_intermediate_size=128, sliding_window=64,
            experts_held=(4, 8), max_position_embeddings=2048))
    laguna.bfloat16()
    eng = ServingEngine(laguna, ServingConfig(
        num_slots=8, page_size=16, pages_per_slot=88, prefill_chunk=256,
        prefix_cache=False))
    eng.submit(np.arange(300, dtype=np.int32) % 512, 2)
    for _ in range(3):
        eng.step()
    eng.drain(0)
    lower("laguna", eng)
