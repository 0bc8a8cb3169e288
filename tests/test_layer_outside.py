"""The trainer's loop order (distributed/hybrid.py ``_forward_loss``): on a
one-stage mesh the layer scan is outside and the micro-batch loop inside,
under ``pp > 1`` the pipeline's schedule (distributed/pipeline.py) keeps the
micro-batches outside. Values against a plain reference that applies the
eager model micro-batch by micro-batch in Python and sums (float32, toy
widths, the CPU); the nesting is read off the step's jaxpr."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
from paddle_tpu.distributed.mesh import create_mesh
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.static.functional import state_tensors

SEQ, VOCAB, MICRO = 32, 128, 2


def dense_config(layers):
    return GPTConfig(vocab_size=VOCAB, hidden_size=64, num_layers=layers,
                     num_heads=4, max_seq_len=SEQ)


def olmoe_config(layers):
    """``GPTConfig.olmoe_1b_7b()``'s block at toy widths: 8 experts of
    width 32, 2 a token, none dropped."""
    import dataclasses

    return dataclasses.replace(
        GPTConfig.olmoe_1b_7b(), vocab_size=VOCAB, hidden_size=64,
        num_layers=layers, num_heads=4, max_seq_len=SEQ, ffn_hidden_size=64,
        moe_num_experts=8, moe_top_k=2, moe_expert_width=32)


CONFIGS = {"dense": dense_config, "olmoe": olmoe_config}


def trainer_of(model, n_micro, pp=1, recompute=False, remat_policy=None,
               v_virtual=None):
    s = DistributedStrategy()
    s.recompute = recompute
    mesh = create_mesh({"dp": 1, "pp": pp, "tp": 1, "sp": 1},
                       jax.devices()[:pp])
    opt = paddle.optimizer.SGD(0.0, parameters=model.parameters())
    return HybridPipelineTrainer(model, opt, s, mesh, n_micro=n_micro,
                                 remat_policy=remat_policy,
                                 v_virtual=v_virtual)


def reference(model, tokens, n_micro):
    """Loss, every parameter's gradient and the blocks' counts: the eager
    model on one micro-batch at a time, summed in Python (the loss and the
    gradients averaged, as the trainer's head averages over all tokens)."""
    names = state_tensors(model)[0]
    loss, grads, stats = 0.0, {n: 0.0 for n in names}, {}
    for mb in np.split(tokens, n_micro):
        for p in model.parameters():
            p.clear_grad()
        one = model.loss(paddle.to_tensor(mb))
        one.backward()
        loss += float(one.numpy()) / n_micro
        for n, p in zip(names, model.parameters()):
            grads[n] = grads[n] + np.asarray(p.grad._value) / n_micro
        for blk in model.blocks:
            for k, v in blk.aux_stats.items():
                stats[k] = stats.get(k, 0.0) + np.asarray(v._value)
    for p in model.parameters():
        p.clear_grad()
    return loss, grads, stats


def trainer_step_values(tr, tokens):
    """What one step differentiates: ``(loss, stats)`` and the gradient of
    every leaf, by the model's own parameter names."""
    key = jax.random.PRNGKey(0)
    (loss, stats), (g_blocks, g_other) = jax.jit(jax.value_and_grad(
        lambda b, o: tr._forward_loss(b, o, (jnp.asarray(tokens),), key),
        argnums=(0, 1), has_aux=True))(tr.block_vals, tr.other_vals)
    grads = dict(zip(tr.other_names, g_other))
    for sfx, g in g_blocks.items():
        # [1, L, ...] or, interleaved, [1, v, L/v, ...]
        g = np.asarray(g).reshape((tr.n_layers,)
                                  + g.shape[3 if tr.v > 1 else 2:])
        for i in range(tr.n_layers):
            grads[f"blocks.{i}.{sfx}"] = g[i]
    return float(loss), grads, jax.device_get(stats)


CASES = [  # kind, layers, n_micro, recompute, remat_policy
    ("dense", 3, 1, False, None),
    ("dense", 3, 2, True, None),
    ("dense", 2, 4, True, None),
    ("dense", 3, 4, False, None),
    ("dense", 2, 2, True, "dots"),
    ("olmoe", 2, 1, True, None),
    ("olmoe", 3, 2, False, None),
    ("olmoe", 3, 2, True, None),
    ("olmoe", 2, 4, True, None),
    ("olmoe", 2, 4, False, None),
    ("olmoe", 2, 2, True, "dots"),
]


@pytest.mark.parametrize("kind,layers,n_micro,recompute,policy", CASES)
def test_one_stage_step_equals_the_micro_batches_summed_in_python(
        kind, layers, n_micro, recompute, policy):
    """Seen (float32, XLA:CPU): the loss within 1.5e-7 relative, every
    gradient leaf within 3.5e-7 to 5.5e-7 of its norm (4.3e-7 at one
    micro-batch, where no order of summation is in play: the eager
    reference's own rounding), the counts exactly."""
    paddle.seed(11)
    model = GPT(CONFIGS[kind](layers))
    tokens = np.random.default_rng(5).integers(
        0, VOCAB, (n_micro * MICRO, SEQ), dtype=np.int32)
    want_loss, want_grads, want_stats = reference(model, tokens, n_micro)
    tr = trainer_of(model, n_micro, recompute=recompute,
                    remat_policy=policy)
    loss, grads, stats = trainer_step_values(tr, tokens)
    assert loss == pytest.approx(want_loss, rel=1e-6)
    assert sorted(grads) == sorted(want_grads)
    for name, want in want_grads.items():
        off = np.linalg.norm(np.asarray(grads[name]) - want)
        assert off <= 1e-6 * np.linalg.norm(want), name
    assert sorted(stats) == sorted(want_stats)
    for name, want in want_stats.items():
        np.testing.assert_array_equal(stats[name], want, err_msg=name)
    if kind == "olmoe":
        assert stats["moe/rows"].sum() == tokens.size * 2 * layers
        # and the step itself hands the same counts out
        tr.step(tokens)
        for name, want in want_stats.items():
            np.testing.assert_array_equal(
                jax.device_get(tr.aux_stats[name]), want, err_msg=name)


def test_interleaved_chunks_on_one_stage_are_the_layers_in_order():
    paddle.seed(11)
    model = GPT(dense_config(4))
    tokens = np.random.default_rng(5).integers(0, VOCAB, (4, SEQ),
                                               dtype=np.int32)
    plain = trainer_step_values(trainer_of(model, 2), tokens)
    chunked = trainer_step_values(trainer_of(model, 2, v_virtual=2), tokens)
    assert chunked[0] == plain[0]
    for name, want in plain[1].items():
        np.testing.assert_array_equal(np.asarray(chunked[1][name]),
                                      np.asarray(want), err_msg=name)


# --- the nesting, read off the step's jaxpr ---------------------------------
def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def scan_nestings(jaxpr, outer=()):
    """Every ``(lengths of the scans around it..., its own length)`` of
    the ``scan``s of ``jaxpr``, through every sub-jaxpr (pjit, remat,
    custom derivatives, shard_map, cond, while)."""
    found = []
    for eqn in jaxpr.eqns:
        inside = outer
        if eqn.primitive.name == "scan":
            inside = outer + (eqn.params["length"],)
            found.append(inside)
        for sub in _sub_jaxprs(eqn):
            found.extend(scan_nestings(sub, inside))
    return found


def step_jaxpr(tr, tokens):
    """The whole compiled step (forward, backward, update) as a jaxpr,
    traced as ``aot_lower`` traces it."""
    tr._build(1)
    return tr._step_fn.trace(
        *tr._state_args(), (tr._stage_arg(tokens),),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).jaxpr.jaxpr


def _holds(nesting, outer, inner):
    """A scan of length ``inner`` somewhere inside one of ``outer``."""
    return any(a == outer and inner in nesting[i + 1:]
               for i, a in enumerate(nesting))


@pytest.mark.parametrize("kind", sorted(CONFIGS))
@pytest.mark.parametrize("recompute", [False, True])
def test_one_stage_step_scans_layers_outside_micro_batches_inside(
        kind, recompute):
    layers, n_micro = 3, 5          # lengths no other loop of the step has
    model = GPT(CONFIGS[kind](layers))
    tokens = np.zeros((n_micro * MICRO, SEQ), np.int32)
    tr = trainer_of(model, n_micro, recompute=recompute)
    nestings = scan_nestings(step_jaxpr(tr, tokens))
    # forward and backward: a layer's parameters enter a body once, the
    # micro-batches loop inside it
    assert sum(_holds(n, layers, n_micro) for n in nestings) >= 2, nestings
    assert not any(_holds(n, n_micro, layers) for n in nestings), nestings


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_two_stage_step_keeps_the_pipelines_schedule(kind):
    pp, layers, n_micro = 2, 6, 5   # 3 layers a stage, 6 ticks
    model = GPT(CONFIGS[kind](layers))
    tokens = np.zeros((n_micro * MICRO, SEQ), np.int32)
    tr = trainer_of(model, n_micro, pp=pp)
    nestings = scan_nestings(step_jaxpr(tr, tokens))
    ticks, lps = n_micro + pp - 1, layers // pp
    # the tick scan is the micro-batch clock, a stage's layers inside it
    assert sum(_holds(n, ticks, lps) for n in nestings) >= 2, nestings
    assert not any(n_micro in n for n in nestings), nestings
    assert not any(_holds(n, lps, ticks) for n in nestings), nestings


def test_pipeline_apply_refuses_a_one_stage_mesh():
    from paddle_tpu.distributed.pipeline import pipeline_apply

    mesh = create_mesh({"dp": 1, "pp": 1, "tp": 1, "sp": 1},
                       jax.devices()[:1])
    with pytest.raises(ValueError, match="one-stage mesh"):
        pipeline_apply(mesh, lambda p, x: x, {"w": jnp.zeros((1, 2, 3))},
                       jnp.zeros((4, 3)), 2)
