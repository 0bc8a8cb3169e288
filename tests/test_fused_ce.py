"""The fused lm-head + cross-entropy (ops/fused_ce.py): loss and gradients
against autodifferentiation of a plain float32 reference, on one device,
under GSPMD (vocabulary over ``tp``, batch over ``dp``) and inside a
``shard_map`` that is manual over ``pp`` alone, as the pipeline's
``head_fn`` runs; a count of the products by the vocabulary, so that a
recomputed pass cannot come back unseen; and the head shared by the
pipeline's stages (``pipeline_apply(head_fn=...)``) against the head on
the whole batch.

The rule makes each chunk's gradients from the tile its loss was made from
(a ``jax.custom_vjp`` whose forward rule saves ``dx`` and ``dW``); nothing
else in the tree holds its gradients: the cells' checks compare a loss.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.ops.fused_ce import (IGNORE, fused_linear_cross_entropy,
                                     fused_linear_cross_entropy_fn,
                                     shifted_labels)
from paddle_tpu.profiler import metrics

B, S, H, V = 2, 48, 16, 40

#: largest |got - want| over largest |want|, a gradient. float32 inputs:
#: rounding of sums alone (seen 2.4e-7 at most). bf16 inputs: ``dx`` and
#: ``dW`` are bf16 (one rounding of ``dx``; ``dW`` rounds once a chunk into
#: its bf16 running sum, as the transposed scan's did): seen 6.7e-3 at most,
#: and autodifferentiation of the ``jax.checkpoint``-ed scan this rule
#: replaced read the same extremes, 2.4e-7 and 6.7e-3, on the same draws.
GRAD_TOL = {"float32": 5e-6, "bfloat16": 1.5e-2}
LOSS_RTOL = 2e-6        # float32 sums of the same products, either dtype


def reference(x, w, bias, labels, w_is_vh):
    """``logits = x @ W^T``, ``log_softmax``, mean over kept positions, in
    float32 from whatever the inputs hold."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    logits = jnp.einsum("bsh,vh->bsv" if w_is_vh else "bsh,hv->bsv", x, w,
                        precision="highest")
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    kept = labels != IGNORE
    gold = jnp.take_along_axis(
        logp, jnp.clip(labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(kept, gold, 0.0)) / jnp.maximum(
        jnp.sum(kept), 1)


def draw(dtype, w_is_vh, bias, ignore, seed=0, b=B, s=S, h=H, v=V):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k[0], (b, s, h)).astype(dtype)
    w = (0.3 * jax.random.normal(k[1], (v, h) if w_is_vh else (h, v))
         ).astype(dtype)
    bs = jax.random.normal(k[2], (v,)).astype(dtype) if bias else None
    labels = jax.random.randint(k[3], (b, s), 0, v)
    if ignore == "some":
        labels = labels.at[0, 3:9].set(IGNORE).at[1, -1].set(IGNORE)
    elif ignore == "chunk":      # positions 16-31: a whole chunk of 16
        labels = labels.at[:, 16:32].set(IGNORE)
    elif ignore == "all":
        labels = jnp.full_like(labels, IGNORE)
    return x, w, bs, labels


def rel_err(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30))


def agree(got, want, dtype):
    """(loss, grads) of the head against (loss, grads) of the reference."""
    (gl, gg), (wl, wg) = got, want
    assert np.isfinite(float(gl))
    np.testing.assert_allclose(float(gl), float(wl), atol=1e-30,
                               rtol=LOSS_RTOL)
    for name, g, r in zip(("dx", "dW", "dbias"), gg, wg):
        assert g.dtype == r.dtype and g.shape == r.shape, name
        if float(jnp.max(jnp.abs(r))) == 0.0:      # every label ignored
            assert float(jnp.max(jnp.abs(g))) == 0.0, name
            continue
        err = rel_err(g, r)
        assert err <= GRAD_TOL[dtype], (name, err)


def head_and_reference(w_is_vh, labels, chunk, cotangent, next_token=False):
    """Two functions of ``(x, w[, bias])``, the tape-level entry (which
    holds ``next_token``) and the reference on the labels it means."""
    ref_labels = shifted_labels(labels) if next_token else labels

    def head(x, w, *bias):
        return cotangent * fused_linear_cross_entropy(
            Tensor(x), Tensor(w), Tensor(labels), chunk=chunk,
            transpose_w=not w_is_vh, bias=Tensor(bias[0]) if bias else None,
            next_token=next_token)._value

    def ref(x, w, *bias):
        return cotangent * reference(x, w, bias[0] if bias else None,
                                     ref_labels, w_is_vh)
    return head, ref


def _cases():
    base = dict(ignore="some", next_token=False, chunk=16, cotangent=1.0)
    for layout in ("vh", "hv"):
        for dtype in ("float32", "bfloat16"):
            for bias in (False, True):
                yield dict(base, layout=layout, dtype=dtype, bias=bias)
        for vary in (dict(ignore="none"), dict(ignore="chunk"),
                     dict(ignore="all"), dict(next_token=True),
                     dict(chunk=None), dict(chunk=32),   # 48 % 32: halves
                     dict(cotangent=3.0)):
            yield dict(base, layout=layout, dtype="bfloat16",
                       bias=layout == "vh", **vary)


def _case_id(c):
    return "-".join(f"{k}={v}" for k, v in c.items())


@pytest.mark.parametrize("case", list(_cases()), ids=_case_id)
def test_loss_and_gradients_match_the_float32_reference(case):
    w_is_vh, dtype = case["layout"] == "vh", case["dtype"]
    x, w, bias, labels = draw(dtype, w_is_vh, case["bias"], case["ignore"])
    args = (x, w) + ((bias,) if case["bias"] else ())
    head, ref = head_and_reference(w_is_vh, labels, case["chunk"],
                                   case["cotangent"], case["next_token"])
    argnums = tuple(range(len(args)))
    got = jax.jit(jax.value_and_grad(head, argnums))(*args)
    want_l, want_g = jax.value_and_grad(ref, argnums)(*args)
    agree(got, (want_l, tuple(g.astype(dtype) for g in want_g)), dtype)
    if case["ignore"] == "all":
        assert float(got[0]) == 0.0          # n = 0 gives 0, not NaN
    # the primal alone is the forward rule's loss: the same arithmetic on
    # the same tile. Bit for bit wherever XLA reduces both alike; its CPU
    # backend picks the sum-exp's reduction tree fusion by fusion (windows
    # of 32 in the loss-only program, one pass beside the gradient's
    # exponentials), which moves the last place: seen 0 and 1 ulp
    np.testing.assert_allclose(float(jax.jit(head)(*args)), float(got[0]),
                               rtol=4 * 2.0 ** -24, atol=0)


def test_eager_tape_backward_uses_the_saved_gradients():
    """``loss.backward()`` on the tape (``jax.vjp`` of the entry) gives the
    reference's gradients in ``.grad``."""
    x, w, bias, labels = draw("float32", True, True, "some")
    tx, tw, tb = (Tensor(a, stop_gradient=False) for a in (x, w, bias))
    loss = fused_linear_cross_entropy(tx, tw, Tensor(labels), chunk=16,
                                      bias=tb)
    loss.backward()
    want = jax.value_and_grad(
        lambda *a: reference(*a, labels, True), (0, 1, 2))(x, w, bias)
    agree((loss._value, tuple(t.grad._value for t in (tx, tw, tb))), want,
          "float32")


def test_second_order_is_right_and_forward_mode_says_no():
    """A custom rule gives up nothing a caller uses: grad of grad
    differentiates the forward rule's own arithmetic; forward mode is
    refused by jax, in words."""
    x, w, _, labels = draw("float32", True, False, "some", s=16)

    def sq_grad(f):
        return jax.grad(lambda x: jnp.sum(jax.grad(f)(x) ** 2))(x)

    got = sq_grad(lambda x: fused_linear_cross_entropy_fn(
        x, w, labels, chunk=8))
    want = sq_grad(lambda x: reference(x, w, None, labels, True))
    assert rel_err(got, want) <= 1e-4
    with pytest.raises(TypeError, match="forward-mode"):
        jax.jvp(lambda x: fused_linear_cross_entropy_fn(x, w, labels),
                (x,), (x,))


# --- sharded: GSPMD over (dp, tp), and manual over pp as head_fn runs -----

def _mesh(**axes):
    n = int(np.prod(list(axes.values())))
    return Mesh(np.array(jax.devices()[:n]).reshape(tuple(axes.values())),
                tuple(axes))


def _placed(mesh, x, w, w_is_vh):
    """``x``'s batch over ``dp``, ``w``'s vocabulary over ``tp``."""
    w_spec = P("tp", None) if w_is_vh else P(None, "tp")
    return (jax.device_put(x, NamedSharding(mesh, P("dp"))),
            jax.device_put(w, NamedSharding(mesh, w_spec)), w_spec)


@pytest.mark.parametrize("layout", ["vh", "hv"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_vocabulary_over_tp_and_batch_over_dp(layout, dtype):
    w_is_vh = layout == "vh"
    mesh = _mesh(dp=2, tp=2)
    x, w, bias, labels = draw(dtype, w_is_vh, True, "some", b=4)
    head = jax.value_and_grad(
        lambda x, w, b: 3.0 * fused_linear_cross_entropy_fn(
            x, w, labels, chunk=16, transpose_w=not w_is_vh, bias=b),
        (0, 1, 2))
    want_l, want_g = jax.value_and_grad(
        lambda x, w, b: 3.0 * reference(x, w, b, labels, w_is_vh),
        (0, 1, 2))(x, w, bias)

    xs, ws, w_spec = _placed(mesh, x, w, w_is_vh)
    got = jax.jit(head)(
        xs, ws, jax.device_put(bias, NamedSharding(mesh, P("tp"))))
    agree(got, (want_l, tuple(g.astype(dtype) for g in want_g)), dtype)
    # dW stays where its vocabulary shard is
    assert got[1][1].sharding.is_equivalent_to(
        NamedSharding(mesh, w_spec), 2)


@pytest.mark.parametrize("layout", ["vh", "hv"])
def test_inside_a_region_manual_over_pp_only(layout):
    """As ``pipeline_apply`` runs ``head_fn`` where the micro-batches do
    not divide among the stages: every stage computes the head on its own
    buffer, the last stage's loss is kept and summed over ``pp``; ``tp``
    and ``dp`` stay GSPMD's inside the region. (Where they divide the
    stages share the head: the tests at the end of this file.)"""
    w_is_vh, dtype = layout == "vh", "float32"
    mesh = _mesh(pp=2, dp=2, tp=2)
    x, w, _, labels = draw(dtype, w_is_vh, False, "some", b=4)

    def loss(x, w):
        @jax.shard_map(mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
                       check_vma=False, axis_names=frozenset({"pp"}))
        def region(x, w, labels):
            stage = jax.lax.axis_index("pp")
            # the other stage holds another buffer, as a pipeline's does
            x = x * jnp.where(stage == 1, 1.0, 0.5).astype(x.dtype)
            out = fused_linear_cross_entropy_fn(
                x, w, labels, chunk=16, transpose_w=not w_is_vh)
            return jax.lax.psum(jnp.where(stage == 1, out, 0.0), "pp")
        return region(x, w, labels)

    got = jax.jit(jax.value_and_grad(loss, (0, 1)))(
        *_placed(mesh, x, w, w_is_vh)[:2])
    want = jax.value_and_grad(
        lambda x, w: reference(x, w, None, labels, w_is_vh), (0, 1))(x, w)
    agree(got, want, dtype)


# --- the count of passes ----------------------------------------------------

def _eqns(jaxpr, inside=()):
    """Every equation of a jaxpr and of the jaxprs its equations hold,
    with the names of the primitives it lies inside."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, inside + (eqn.primitive.name,))


def vocabulary_products(fn, *args, v):
    """(dot_generals with an operand of more than 1 dimension one of which
    is the vocabulary, i.e. not the one-hot's reduce; those inside a
    remat)."""
    found, in_remat = 0, 0
    for eqn, inside in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        (lc, rc), (lb, _) = eqn.params["dimension_numbers"]
        lhs, rhs = (a.aval.shape for a in eqn.invars)
        if lb:                       # the gold logit's batched contraction
            continue
        free_or_contracted = (
            [lhs[i] for i in lc] + [d for i, d in enumerate(lhs)
                                    if i not in lc]
            + [d for i, d in enumerate(rhs) if i not in rc])
        if v in free_or_contracted:
            found += 1
            in_remat += any("remat" in n or "checkpoint" in n
                            for n in inside)
    return found, in_remat


@pytest.mark.parametrize("layout", ["vh", "hv"])
@pytest.mark.parametrize("bias", [False, True])
def test_a_step_multiplies_by_the_vocabulary_three_times(layout, bias):
    w_is_vh = layout == "vh"
    x, w, bs, labels = draw("bfloat16", w_is_vh, bias, "some", v=56)
    grad = jax.grad(lambda x, w: fused_linear_cross_entropy_fn(
        x, w, labels, chunk=16, transpose_w=not w_is_vh, bias=bs), (0, 1))
    assert vocabulary_products(grad, x, w, v=56) == (3, 0)
    # evaluation makes the tile alone
    assert vocabulary_products(
        lambda x, w: fused_linear_cross_entropy_fn(
            x, w, labels, chunk=16, transpose_w=not w_is_vh, bias=bs),
        x, w, v=56) == (1, 0)


def _counted(name, keys):
    """{key: the counter ``name % key``'s value, 0 before its first add}."""
    snap = metrics.registry().snapshot()
    return {k: snap.get(name % k, {"value": 0})["value"] for k in keys}


def _traces():
    return _counted("head/fused_ce_traces{rule=%s}",
                    ("grad_in_forward", "loss_only"))


def test_the_counter_says_which_rule_a_program_compiled():
    """``head/fused_ce_traces{rule=}``: a trainer's first step traces the
    rule that makes the gradients in the forward pass, once, and never the
    other; ``GPT.loss`` with no gradient asked traces the loss alone."""
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.hybrid import HybridPipelineTrainer
    from paddle_tpu.distributed.strategy_compiler import \
        build_mesh_from_strategy
    from paddle_tpu.models import gpt_tiny

    paddle.seed(3)
    net = gpt_tiny()
    tokens = np.random.RandomState(0).randint(
        0, net.config.vocab_size, (8, 32)).astype(np.int32)
    before = _traces()
    with paddle.no_grad():
        alone = float(net.loss(paddle.to_tensor(tokens)).numpy())
    after = _traces()
    assert after["loss_only"] - before["loss_only"] == 1
    assert after["grad_in_forward"] == before["grad_in_forward"]

    opt = paddle.optimizer.SGD(0.0, parameters=net.parameters())
    s = DistributedStrategy()
    s.hybrid_configs = {}
    tr = HybridPipelineTrainer(net, opt, s, build_mesh_from_strategy(s),
                               n_micro=2)
    first = float(tr.step(tokens))
    tr.step(tokens)                          # a second step traces nothing
    step = _traces()
    assert step["grad_in_forward"] - after["grad_in_forward"] == 1
    assert step["loss_only"] == after["loss_only"]
    np.testing.assert_allclose(first, alone, rtol=2e-5)


# --- the pipeline's stages share the head (distributed/pipeline.py) ---------
# pipeline_apply(head_fn=...) deals the last stage's finished micro-batches
# out over ``pp`` and every stage runs the head on its own. The reference is
# the head the parent ran, on the whole batch at once: the pipeline's
# activations leave the region by the egress that has no head (code this
# form does not touch) and one head call sees every row. A benchmark cell's
# check cannot stand in for these: it puts one sequence in every row, so a
# dropped or doubled share reads the same loss.

P_MB, P_S, P_H, P_V = 2, 16, 8, 40


def _share_traces():
    return _counted("head/pp_share_traces{stages=%d}", (1, 2))


def _pipeline_problem(mesh, v, n_micro, uneven):
    """Two stages of ``v`` chunks of one layer, an embedding, a head over
    ``tp``; every row of the batch its own tokens. ``uneven``: nearly every
    label of the first half of the batch (stage 0's share) is ignored."""
    b = n_micro * P_MB
    k = jax.random.split(jax.random.PRNGKey(7 + n_micro), 6)
    stack = (2,) + ((v,) if v > 1 else ()) + (1, P_H, P_H)
    blocks = {"w": 0.4 * jax.random.normal(k[0], stack)}
    w_head = 0.3 * jax.random.normal(k[1], (P_V, P_H))
    emb = jax.random.normal(k[2], (P_V, P_H))
    extra = 0.1 * jax.random.normal(k[3], (b, P_S, P_H))
    tokens = jax.random.randint(k[4], (b, P_S), 0, P_V)
    labels = jax.random.randint(k[5], (b, P_S), 0, P_V)
    if uneven:
        labels = labels.at[:b // 2, 2:].set(IGNORE).at[-1, :3].set(IGNORE)
    rows = P("dp") if "dp" in mesh.shape else P()
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    args = ({"w": put(blocks["w"], P("pp"))}, put(w_head, P("tp", None)),
            put(emb, P()), put(extra, rows))
    return args, put(tokens, rows), put(labels, rows)


def _stage(params, x):
    for l in range(params["w"].shape[0]):
        x = x + jnp.tanh(x @ params["w"][l])
    return x


def _pipeline_losses(mesh, v, n_micro, tokens, labels, terms):
    """(the shared head's loss, the whole-batch head's) as functions of
    (blocks, head weights, embedding, an addend of x)."""
    from paddle_tpu.distributed.pipeline import pipeline_apply

    def head(rows, w, lbl):
        loss = fused_linear_cross_entropy_fn(rows, w, lbl, chunk=8)
        return ((loss, jnp.sum(lbl != IGNORE)),) if terms else loss

    def shared(blocks, w_head, emb, extra):
        return pipeline_apply(
            mesh, _stage, blocks, emb[tokens] + extra, n_micro,
            v_virtual=v, head_fn=head, head_args=(w_head,),
            head_batch=(labels,))

    def whole(blocks, w_head, emb, extra):
        out = pipeline_apply(mesh, _stage, blocks, emb[tokens] + extra,
                             n_micro, v_virtual=v)
        return fused_linear_cross_entropy_fn(out, w_head, labels, chunk=8)
    return shared, whole


def _agree_all(got, want):
    (gl, gg), (wl, wg) = got, want
    np.testing.assert_allclose(float(gl), float(wl), rtol=LOSS_RTOL)
    for g, r in zip(jax.tree_util.tree_leaves(gg),
                    jax.tree_util.tree_leaves(wg)):
        assert g.shape == r.shape and g.dtype == r.dtype
        assert rel_err(g, r) <= GRAD_TOL["float32"]


@pytest.mark.parametrize("uneven", [False, True], ids=["every", "uneven"])
@pytest.mark.parametrize("v", [1, 2], ids=["gpipe", "interleaved"])
@pytest.mark.parametrize("axes", [dict(pp=2, tp=2), dict(pp=2, dp=2, tp=2)],
                         ids=["pp2tp2", "pp2dp2tp2"])
def test_the_stages_share_the_head(axes, v, uneven):
    """Loss and the gradients of blocks, head weights, embedding and ``x``
    are the whole-batch head's on distinct rows, also where the shares keep
    different counts of labels (``(mean, count)`` terms); the program
    counted ``stages=2``."""
    mesh, n_micro = _mesh(**axes), 4
    args, tokens, labels = _pipeline_problem(mesh, v, n_micro, uneven)
    shared, whole = _pipeline_losses(mesh, v, n_micro, tokens, labels,
                                     terms=uneven)
    argnums = (0, 1, 2, 3)
    before = _share_traces()
    got = jax.jit(jax.value_and_grad(shared, argnums))(*args)
    after = _share_traces()
    assert after[2] > before[2] and after[1] == before[1]
    _agree_all(got, jax.jit(jax.value_and_grad(whole, argnums))(*args))
    if uneven:
        # the test tells the two means apart: a head that gives no counts
        # is weighed a share alike, and is not this batch's mean
        alike = _pipeline_losses(mesh, v, n_micro, tokens, labels,
                                 terms=False)[0]
        assert abs(float(jax.jit(alike)(*args)) - float(got[0])) > 1e-3


@pytest.mark.parametrize("v", [1, 2], ids=["gpipe", "interleaved"])
@pytest.mark.parametrize("uneven", [False, True], ids=["every", "uneven"])
def test_micro_batches_that_do_not_divide_keep_the_whole_head(v, uneven):
    """``n_micro % pp != 0``: every stage runs the head on its whole
    buffer, the last stage's loss is kept, and the program says so."""
    mesh, n_micro = _mesh(pp=2, tp=2), 3
    args, tokens, labels = _pipeline_problem(mesh, v, n_micro, uneven)
    shared, whole = _pipeline_losses(mesh, v, n_micro, tokens, labels,
                                     terms=uneven)
    before = _share_traces()
    got = jax.jit(jax.value_and_grad(shared, (0, 1, 2, 3)))(*args)
    after = _share_traces()
    assert after[1] > before[1] and after[2] == before[2]
    _agree_all(got, jax.jit(jax.value_and_grad(whole, (0, 1, 2, 3)))(*args))


def _vocabulary_operands(fn, *args, v):
    """The shapes of the left operands of the products that contract or
    make the vocabulary with a sequence chunk's states (the logits tile's
    ``x`` chunk among them)."""
    return [eqn.invars[0].aval.shape
            for eqn, _ in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "dot_general"
            and not eqn.params["dimension_numbers"][1][0]
            and v in eqn.outvars[0].aval.shape]


@pytest.mark.parametrize("n_micro,rows", [(4, 2 * P_MB), (3, 3 * P_MB)],
                         ids=["shared", "whole"])
def test_a_shared_head_sees_its_share_and_multiplies_three_times(n_micro,
                                                                 rows):
    """In the step's jaxpr the logits tile is made from ``n_micro / pp``
    micro-batches' rows (all of them where they do not divide), and a step
    still multiplies by the vocabulary three times, none in a remat."""
    mesh = _mesh(pp=2, tp=2)
    args, tokens, labels = _pipeline_problem(mesh, 1, n_micro, False)
    shared, _ = _pipeline_losses(mesh, 1, n_micro, tokens, labels, False)
    grad = jax.grad(shared, (0, 1, 2, 3))
    assert vocabulary_products(grad, *args, v=P_V) == (3, 0)
    tiles = _vocabulary_operands(grad, *args, v=P_V)
    assert tiles and all(t[0] == rows for t in tiles), tiles


def test_a_head_batch_that_is_not_the_batchs_rows_is_refused():
    from paddle_tpu.distributed.pipeline import pipeline_apply

    mesh = _mesh(pp=2, tp=2)
    args, tokens, labels = _pipeline_problem(mesh, 1, 4, False)
    with pytest.raises(ValueError, match="leading dimension"):
        pipeline_apply(mesh, _stage, args[0], args[2][tokens], 4,
                       head_fn=lambda rows, lbl: jnp.sum(rows),
                       head_batch=(labels[:3],))
