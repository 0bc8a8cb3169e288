"""Is what the trainer computes Solar-Open2? Outside the window.

As ``checks/olmoe_train.py``: the losses of the run are finite and fall (or
stay flat at the entropy of random tokens). Then one more step with the
learning rate at 0 on a batch that is one seeded sequence of the timed
length in every row. The plain float32 reference
(``references/solar_open2.py``: the recurrence token by token, a masked
softmax, a loop over the experts) computes the same loss from the trainer's
own weights, **a layer at a time**: one layer's float32 weights are on the
device at once, beside the trainer's state, and attention's scores a block
of rows at a time.

The loss is a mean over 8,191 positions of a model whose last norm hides
much, so each kind of layer is compared on equal inputs too: the program's
own ``kda_mix``, ``gqa_mix`` and expert layer (``held_moe`` with the
held range, as the block calls it) are given the trainer's weights of the
layer and the input the reference's layer had, rounded to the weights'
type, and must give the reference's output. And the step's own counts
(``HybridPipelineTrainer.aux_stats``): every assignment routed is counted,
the rows held add up, none is dropped.

**The backward pass** is most of a step and is compared too, at the timed
length: each mix and each expert layer is pulled back (``jax.vjp``) along
one seeded cotangent, in the program (the scan's ``kda_bwd_*`` kernels,
grouped-query ``flash_bwd_dq``/``flash_bwd_dkv``, the held experts'
hand-written VJP) and in the reference (``jax.vjp`` of the token-by-token
recurrence, the masked softmax, the loop over the experts) on the same
input, and every leaf's gradient and the input's must be the reference's
(``GRAD_RTOL``, the worst leaf).
"""
import contextlib

import numpy as np

from perfbench import loader

#: relative difference allowed between the trainer's bf16 loss and the
#: float32 reference's. Seen on the chip: 2.6e-7 to 9.4e-6 over 9 runs
#: (PERF.md section 2), so about four times the largest. In the sandbox, at
#: a toy size, weights rounded to bf16 move the reference's loss by 4e-7 to
#: 5.5e-6 and weights rounded to fp8 (e4m3) by 5.3e-5 to 1.4e-4
#: (tests/perfbench/test_pb_solar_open2.py): the embedding's rows of 4 make
#: the loss a weaker witness than ``MIX_RTOL`` below, which fp8 fails by 5x
LOSS_RTOL = 4e-5
#: ||program - reference|| / ||reference|| of a mix's output on equal
#: inputs, by kind. Seen on the chip: 6.8e-3 to 7.1e-3 for both kinds over 9
#: runs (the products' bf16 operands), so about three times that. In the
#: sandbox at a small width: bf16 4.6e-3 to 9.9e-3; fp8 weights 0.09 to
#: 0.11; the decay left out 1.02; query head i on key/value head i % group
#: 0.97 to 1.03. A state *stored* in bf16 between chunks reads what bf16
#: operands read (9.7e-3 against 9.7e-3): the products round it to bf16 as
#: an operand anyway, and this limit does not tell the two apart
MIX_RTOL = {"kda": 0.02, "gqa": 0.02}
#: a token whose expert-layer output differs from the reference's by more
#: than this share of its norm was routed or weighed otherwise (one held
#: expert more or less: 0.08 and up; weights renormalised over the held
#: experts alone: 0.5 and up) and not merely rounded (bf16: under 0.02)
TOKEN_RTOL = 0.05
#: ||program's gradient - reference's|| / ||reference's|| of the worst leaf
#: (every weight of the part, and its input), by part; see PERF.md section 2
#: for the readings on either side
GRAD_RTOL = {"kda": 0.03, "gqa": 0.03, "moe": 0.03}


def loss_agrees(got: float, want: float) -> tuple:
    rel = abs(got - want) / abs(want)
    return rel, rel <= LOSS_RTOL


def ref_cfg(c: dict, attention_rows=None) -> dict:
    lin = c["linear_attn_config"]
    return {"heads": c["num_attention_heads"],
            "kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
            "linear_heads": lin["num_heads"],
            "linear_head_dim": lin["head_dim"],
            "top_k": c["num_experts_per_tok"], "eps": c["rms_norm_eps"],
            "attention_rows": attention_rows}


def held_range(c: dict) -> tuple:
    return (c["experts_held_first"], c["n_routed_experts"])


def layer_weights(tr, period: int):
    """One dict a layer from the trainer's stacked periods ``[pp, lps,
    ...]``, under the names a layer gives its weights."""
    for stage in range(tr.pp):
        for p in range(tr.lps):
            for i in range(period):
                head = f"layers.{i}."
                yield {k[len(head):]: np.asarray(v[stage, p])
                       for k, v in tr.block_vals.items()
                       if k.startswith(head)}


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def mix_agrees(w: dict, want: dict, model_cfg) -> dict:
    """The program's mix (``models/solar_open2.kda_mix`` or ``gqa_mix``)
    on one layer's weights ``w`` and the reference's input."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import solar_open2 as prog

    kind = "kda" if "mix.A_log" in w else "gqa"
    mix = {k[4:]: jnp.asarray(v) for k, v in w.items()
           if k.startswith("mix.")}
    fn = prog.kda_mix if kind == "kda" else prog.gqa_mix
    got = jax.jit(lambda x, m: fn(x, m, model_cfg))(
        jnp.asarray(want["mix_in"], w["mix.w_o"].dtype), mix)
    rel = rel_err(got, want["mix_out"])
    return {"kind": kind, "rel": rel, "ok": rel <= MIX_RTOL[kind]}


def cotangent(seed, tag: int, shape):
    """What both sides pull back: seeded, bf16's values."""
    import jax.numpy as jnp

    dy = np.random.default_rng([seed, 1 << 21, tag]).standard_normal(
        shape, np.float32)
    return jnp.asarray(dy, jnp.bfloat16)


def worst_leaf(got: dict, want: dict) -> tuple:
    """(name, relative error) of the leaf whose gradient is farthest from
    the reference's; a leaf the reference gives no gradient (the selection
    bias) must have none."""
    errs = {}
    for name, ref_grad in want.items():
        ref_grad = np.asarray(ref_grad, np.float32)
        mine = np.asarray(got[name], np.float32).reshape(ref_grad.shape)
        norm = float(np.linalg.norm(ref_grad))
        errs[name] = float(np.linalg.norm(mine - ref_grad)) / norm if norm \
            else float(np.abs(mine).max() > 0)
    name = max(errs, key=lambda k: (np.isnan(errs[k]), errs[k]))
    return name, errs[name]


def pulled_back(fn, x, weights: dict, dy, dtype) -> dict:
    """Every leaf's gradient, and ``x``'s, of ``fn(x, weights)`` along
    ``dy``, with all three in ``dtype``; host arrays."""
    import jax
    import jax.numpy as jnp

    cast = lambda a: jnp.asarray(a, dtype)
    dx, dw = jax.jit(lambda x_, w_, dy_: jax.vjp(fn, x_, w_)[1](dy_))(
        cast(x), {k: cast(v) for k, v in weights.items()}, cast(dy))
    return dict(jax.device_get(dw), x=jax.device_get(dx))


def part_of(w: dict, head: str) -> dict:
    return {k: v for k, v in w.items() if k.startswith(head)}


def mix_grads(w: dict, x, dy, model_cfg) -> dict:
    """The program's mix pulled back: its kernels' backward passes, on the
    weights as the trainer holds them."""
    from paddle_tpu.models import solar_open2 as prog

    fn = prog.kda_mix if "mix.A_log" in w else prog.gqa_mix
    return pulled_back(
        lambda x_, m: fn(x_, {k[4:]: v for k, v in m.items()}, model_cfg),
        x, part_of(w, "mix."), dy, w["mix.w_o"].dtype)


def share_grads(w: dict, x, dy, c: dict) -> dict:
    """The program's expert layer, told the held range, pulled back."""
    from paddle_tpu.distributed.moe import held_moe

    return pulled_back(
        lambda x_, m: held_moe(
            x_, m["mlp.gate"], m["mlp.w_gate"], m["mlp.w_up"],
            m["mlp.w_down"], c["num_experts_per_tok"], held_range(c),
            select_bias=m["mlp.select_bias"],
            shared=tuple(m["mlp.shared_" + n]
                         for n in ("gate", "up", "down")))[0],
        x, part_of(w, "mlp."), dy, w["mlp.w_gate"].dtype)


def reference_grads(ref, w: dict, x, dy, rcfg: dict, held=None) -> dict:
    """The reference's mix (``held`` None) or expert layer pulled back,
    float32."""
    import jax
    import jax.numpy as jnp

    if held is not None:
        fn, head = (lambda x_, m: ref.moe(x_, m, rcfg, held)), "mlp."
    else:
        mix = ref.kda_mix if ref.is_kda(w) else ref.gqa_mix
        fn, head = (lambda x_, m: mix(x_, m, rcfg)), "mix."
    with jax.default_matmul_precision("highest"):
        return pulled_back(fn, x, part_of(w, head), dy, jnp.float32)


@contextlib.contextmanager
def optimizer_on_host(tr):
    """The optimizer's moments wait on the host while the reference runs:
    its backward pass through one linear-attention layer at 8,192 tokens
    takes 9.6 GB (compiled for the v5e), which fits beside the trainer's
    weights (2.6 GB) and not beside its moments too (5.2 GB more, of 16.9),
    and its forward pass, run piece by piece, has been seen to take 8.4."""
    import jax

    state = tr.device_state()
    leaves, tree = jax.tree_util.tree_flatten(
        (state["block_opt"], state["other_opt"]))
    held = [(jax.device_get(a), a.sharding) if isinstance(a, jax.Array)
            else None for a in leaves]
    for a, h in zip(leaves, held):
        if h is not None:
            a.delete()
    try:
        yield
    finally:
        block_opt, other_opt = jax.tree_util.tree_unflatten(tree, [
            a if h is None else jax.device_put(*h)
            for a, h in zip(leaves, held)])
        tr.load_device_state(dict(state, block_opt=block_opt,
                                  other_opt=other_opt))


def grads_agree(w: dict, want: dict, model_cfg, c: dict, rcfg: dict, ref,
                seed, layer: int) -> list:
    """One layer's mix and expert layer pulled back along seeded cotangents
    by the program and by the reference, each on the reference's input
    rounded to the weights' type. The expert layer's cotangent is zero on
    the reference's near ties: a token routed the other way there is no
    fault, and would move a held expert's gradient by a row in 200."""
    import jax.numpy as jnp

    dt = w["mix.w_o"].dtype
    kind = "kda" if ref.is_kda(w) else "gqa"
    x = jnp.asarray(want["mix_in"], dt)
    dy = cotangent(seed, 2 * layer, x.shape)
    leaf, rel = worst_leaf(mix_grads(w, x, dy, model_cfg),
                           reference_grads(ref, w, x, dy, rcfg))
    out = [{"kind": kind, "leaf": leaf, "rel": rel,
            "ok": rel <= GRAD_RTOL[kind]}]
    x = jnp.asarray(want["x"], dt)
    dy = jnp.where(jnp.asarray(want["near"])[:, None], 0,
                   cotangent(seed, 2 * layer + 1, x.shape))
    leaf, rel = worst_leaf(
        share_grads(w, x, dy, c),
        reference_grads(ref, w, x, dy, rcfg, held_range(c)))
    return out + [{"kind": "moe", "leaf": leaf, "rel": rel,
                   "ok": rel <= GRAD_RTOL["moe"]}]


def share_agrees(w: dict, want: dict, c: dict) -> dict:
    """The program's expert layer, told the held range, on one layer's
    weights and the reference's input: rows it moved against the
    reference's, tokens whose output is not the reference's within
    ``TOKEN_RTOL``, and whether both are within the near ties."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.moe import held_moe

    dt = w["mlp.w_gate"].dtype
    shared = tuple(jnp.asarray(w["mlp.shared_" + n])
                   for n in ("gate", "up", "down"))
    y, rows = jax.jit(
        lambda x, gate, wg, wu, wd, bias, sh: held_moe(
            x, gate, wg, wu, wd, c["num_experts_per_tok"], held_range(c),
            select_bias=bias, shared=sh))(
        jnp.asarray(want["x"], dt), w["mlp.gate"], w["mlp.w_gate"],
        w["mlp.w_up"], w["mlp.w_down"], w["mlp.select_bias"], shared)
    rows = np.asarray(rows).astype(np.int64)
    want_y = np.asarray(want["y"], np.float32)
    err = np.linalg.norm(np.asarray(y, np.float32) - want_y, axis=-1) \
        / np.linalg.norm(want_y, axis=-1)
    out = {"rows": int(rows.sum()),
           "moved": int(np.abs(rows - np.asarray(want["rows"])).sum()),
           "off": int((err > TOKEN_RTOL).sum()),
           "load": float(rows.max()) * rows.size / max(int(rows.sum()), 1),
           "allowed": int(want["near_ties"])}
    out["ok"] = out["moved"] <= out["allowed"] \
        and out["off"] <= out["allowed"]
    return out


def step_counts(stats: dict, tokens: int, c: dict, layers: int,
                want_rows: int, copies: int, near_ties: int) -> dict:
    """What one step routed, from its ``aux_stats``: whether it counted
    every assignment of ``tokens`` tokens through ``layers`` layers,
    whether the held experts' rows add up to what it says it held, and how
    far that is from the reference's count for one sequence, ``copies``
    times."""
    rows = np.rint(np.asarray(stats["moe/rows"], np.float64))
    held = int(round(float(stats["moe/assigned"])))
    routed = int(round(float(stats["moe/routed"])))
    return {"routed": routed == tokens * c["num_experts_per_tok"] * layers,
            "dropped": held - int(rows.sum()), "held": held,
            "moved": abs(held - copies * want_rows) // copies,
            "near_ties": near_ties}


def reference_pass(ref, tr, c: dict, seq, period: int) -> dict:
    """The reference over one sequence, a layer at a time: the loss, and
    a layer's inputs and outputs as ``ref.layer`` hands them out."""
    import jax
    import jax.numpy as jnp

    cfg = ref_cfg(c, attention_rows=min(1024, len(seq)))
    other = dict(zip(tr.other_names, tr.other_vals))
    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)
    infos = []
    with jax.default_matmul_precision("highest"):
        x = f32(other["wte.weight"])[np.asarray(seq)][None]
        for w in layer_weights(tr, period):
            x, info = ref.layer(x, {k: f32(v) for k, v in w.items()}, cfg,
                                held_range(c))
            infos.append({k: np.asarray(v) for k, v in info.items()})
        logits = ref.rms_norm(x, f32(other["ln_f.weight"]), c["rms_norm_eps"]) \
            @ f32(other["lm_head.weight"])
        loss = float(ref.next_token_loss(logits, seq[None]))
    return {"loss": loss, "layers": infos}


def check(ctx, tr, opt, work, losses) -> dict:
    import jax

    c = ctx.config
    ref = loader.load_module("references", c["reference"])
    fam = loader.load_module("families", c["family"])
    finite = bool(np.isfinite(losses).all())
    falling = np.mean(losses[-3:]) <= 1.005 * np.mean(losses[:3])

    rows = work["micro"] * work["n_micro"]
    seq = np.random.default_rng([ctx.seed, 1 << 20]).integers(
        0, c["vocab_size"], work["seq"], dtype=np.int32)
    lr = opt.get_lr()
    opt.set_lr(0.0)
    try:
        got = float(jax.block_until_ready(tr.step(np.tile(seq, (rows, 1)))))
    finally:
        opt.set_lr(lr)
    stats = jax.device_get(tr.aux_stats)

    model_cfg = fam.model_config(c)
    rcfg = ref_cfg(c, attention_rows=min(1024, len(seq)))
    with optimizer_on_host(tr):
        want = reference_pass(ref, tr, c, seq, fam.PERIOD)
        weights = list(layer_weights(tr, fam.PERIOD))
        mixes = [mix_agrees(w, r, model_cfg)
                 for w, r in zip(weights, want["layers"])]
        shares = [share_agrees(w, r, c)
                  for w, r in zip(weights, want["layers"])]
        grads = [g for i, (w, r) in enumerate(zip(weights, want["layers"]))
                 for g in grads_agree(w, r, model_cfg, c, rcfg, ref,
                                      ctx.seed, i)]
    rel, agrees = loss_agrees(got, want["loss"])
    step = step_counts(
        stats, rows * work["seq"], c, len(weights),
        int(sum(r["rows"].sum() for r in want["layers"])), rows,
        int(sum(r["near_ties"] for r in want["layers"])))
    ok = finite and falling and agrees and all(m["ok"] for m in mixes) \
        and all(s["ok"] for s in shares) and all(g["ok"] for g in grads) \
        and step["routed"] and step["dropped"] == 0
    return {"ok": ok,
            "note": f"check: losses finite {finite}, falling "
            f"{bool(falling)}; one sequence's loss {got:.5f} by the "
            f"trainer, {want['loss']:.5f} by the float32 reference (rel "
            f"{rel:.2e}, allowed {LOSS_RTOL:.0e}); that step: every routed "
            f"assignment counted {step['routed']}, dropped "
            f"{step['dropped']}, rows held {step['held']}, a sequence's "
            f"{step['moved']} from the reference's (near ties there "
            f"{step['near_ties']}); each layer's mix on the reference's "
            "input: " + ", ".join(
                f"{m['kind']} rel {m['rel']:.2e} (allowed "
                f"{MIX_RTOL[m['kind']]:.0e})" for m in mixes)
            + "; its expert layer's share: " + "; ".join(
                f"rows {s['rows']}, moved {s['moved']}, tokens off "
                f"{s['off']} (near ties allow {s['allowed']}), load "
                f"max/mean {s['load']:.2f}" for s in shares)
            + "; each part's gradients along one cotangent, the worst leaf "
            "against the reference's: " + ", ".join(
                f"{g['kind']} {g['leaf']} rel {g['rel']:.2e} (allowed "
                f"{GRAD_RTOL[g['kind']]:.0e})" for g in grads)}
