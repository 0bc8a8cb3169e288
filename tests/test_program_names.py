"""The names the program gives its parts (ISSUE 24): ``jax.named_scope``
names baked into the serving tick and the training step by
``profiler.trace.annotate``, and ``name=`` on the Pallas calls. They are
metadata: the compiled program is the same with and without them."""
import contextlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.profiler import trace
from paddle_tpu.serving import ServingConfig, ServingEngine

TICK_NAMES = ("blk/qkv", "blk/attn", "blk/attn_out", "blk/ffn",
              "blk/kv_scatter", "tick/embed", "tick/head", "tick/sample")
STEP_NAMES = ("fwd/stem", "fwd/blocks", "fwd/head", "opt/update", "blk/qkv",
              "blk/attn", "blk/attn_out", "blk/ffn")


def lowered_tick():
    """The unified tick of a toy engine, lowered from the avals of its
    first dispatch."""
    paddle.seed(0)
    net = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=64))
    net.eval()
    eng = ServingEngine(net, ServingConfig(num_slots=2, page_size=16))
    eng.submit(np.arange(5, dtype=np.int32), 2)
    eng.step()
    eng.drain(0)
    fn, avals = eng._program_args[eng.compiled_sites[0]]
    return fn.lower(*avals)


@pytest.fixture(scope="module")
def tick_text():
    return lowered_tick().as_text(debug_info=True)


@pytest.fixture(scope="module")
def step_text():
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.mesh import create_mesh

    paddle.seed(0)
    model = GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                          num_heads=2, max_seq_len=32))
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    s = DistributedStrategy()
    s.recompute = True
    mesh = create_mesh({"dp": 1, "pp": 1, "tp": 1, "sp": 1},
                       jax.devices()[:1])
    tr = HybridPipelineTrainer(model, opt, s, mesh, n_micro=2)
    batch = jax.ShapeDtypeStruct((4, 32), np.int32)
    return tr.aot_lower(batch).as_text(debug_info=True)


@pytest.mark.parametrize("name", TICK_NAMES)
def test_the_tick_names_its_parts(tick_text, name):
    assert re.search(rf'loc\("[^"]*\b{name}/', tick_text), name


def test_the_kv_scatter_is_named_inside_the_attention(tick_text):
    assert "blk/attn/blk/kv_scatter/" in tick_text


@pytest.mark.parametrize("name", STEP_NAMES)
def test_the_training_step_names_its_parts(step_text, name):
    assert re.search(rf'loc\("[^"]*\b{name}\b', step_text), name


def test_backward_operations_inherit_the_forward_names(step_text):
    """Through ``transpose(jvp(...))`` and ``checkpoint`` prefixes, so a
    reader matches a name anywhere on the path."""
    found = set(re.findall(r'loc\("([^"]*)"', step_text))
    assert any("transpose(jvp(fwd/blocks))/" in p for p in found)
    assert any("rematted_computation/blk/attn/" in p for p in found)
    assert any("checkpoint" in p and "blk/ffn/" in p for p in found)


def pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    pallas_names(sub, out)
    return out


def flash_grad_names(seq):
    from paddle_tpu.ops import flash_attention as fa

    q = jnp.ones((1, seq, 2, 64), jnp.bfloat16)

    def loss(q, k, v):
        return fa._flash_mha(q, k, v, True, 0.125).astype(jnp.float32).sum()

    return pallas_names(
        jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q).jaxpr, [])


def ragged_names():
    from paddle_tpu.ops.paged_attention import ragged_paged_attention

    pool = jnp.ones((9, 16, 2, 64), jnp.bfloat16)

    def f(q):
        return ragged_paged_attention(
            q, pool, pool, jnp.zeros((2, 4), jnp.int32),
            jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32), impl="pallas")

    return pallas_names(
        jax.make_jaxpr(f)(jnp.ones((2, 1, 2, 64), jnp.bfloat16)).jaxpr, [])


@pytest.mark.parametrize("name,calls", [
    ("flash_fwd", lambda: flash_grad_names(128)),
    ("flash_bwd", lambda: flash_grad_names(128)),       # one tile: fused
    ("flash_bwd_dq", lambda: flash_grad_names(2048)),
    ("flash_bwd_dkv", lambda: flash_grad_names(2048)),
    ("ragged_paged_attn", ragged_names),
])
def test_a_pallas_call_carries_its_name(name, calls):
    names = calls()
    assert name in names and None not in names, names


def stripped(hlo: str) -> str:
    """Optimized HLO text without what only names things: op metadata, the
    tables of files and stack frames it points into, and instruction
    names (numbered in order of appearance instead)."""
    hlo = re.sub(r', metadata=\{[^{}]*("[^"]*"[^{}]*)*\}', "", hlo)
    hlo = "\n".join(
        ln for ln in hlo.splitlines() if not re.match(
            r'^(\d+ ["{].*|FileNames|FunctionNames|FileLocations|'
            r'StackFrames)$', ln))
    seen = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: seen.setdefault(m.group(0), f"%n{len(seen)}"),
                  hlo)


def test_the_names_cost_nothing_in_the_compiled_tick(monkeypatch):
    named = lowered_tick().compile().as_text()
    assert "blk/attn" in named and "tick/sample" in named
    from paddle_tpu.models import gpt

    nothing = lambda name: contextlib.nullcontext()  # noqa: E731
    monkeypatch.setattr(gpt, "annotate", nothing)
    monkeypatch.setattr(trace, "annotate", nothing)
    plain = lowered_tick().compile().as_text()
    assert "blk/attn" not in plain and "tick/sample" not in plain
    assert stripped(named) == stripped(plain)
    assert "metadata=" not in stripped(named)
