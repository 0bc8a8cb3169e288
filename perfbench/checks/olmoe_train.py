"""Is what the trainer computes OLMoE? Outside the window.

As ``checks/gpt_train.py``: the losses of the run are finite and fall (or,
at the entropy of uniformly random tokens, stay flat). Then one more step
with the learning rate at 0 on a batch that is one seeded sequence in every
row (one row a micro-batch, so every micro-batch routes the same tokens):
the loss the trainer reports is that sequence's cross entropy plus 0.01 x
its load-balance term plus 0.001 x its router z-loss at those weights, and
the plain float32 reference computes the same three from the trainer's own
weights.

The loss alone cannot see a dropped token: a capacity of 1.25 drops a third
of this sequence's assignments and moves it no further than bf16 rounding
does (PERF.md section 2). So the routing is compared too. First what that
step itself routed: the trainer hands out, as outputs of the step, the rows
every expert was given, summed over layers and micro-batches
(``HybridPipelineTrainer.aux_stats``). They must sum to the step's tokens x
experts a token x layers, every assignment placed; how far they lie from
the reference's rows is printed and decides nothing, because from the
second layer on the trainer's bf16 activations are not the reference's.
Then every layer alone, on equal inputs. The program's expert layer, ``paddle_tpu.distributed.moe.dropless_moe`` as the
block calls it, is given the trainer's weights of the layer and the input
the reference's expert layer had, rounded to the weights' type. It must
place every assignment (its rows sum to tokens x experts a token); its rows
an expert must be the reference's but for tokens whose last chosen and
first unchosen probabilities are a near tie there (closer than 2^-7 of
their value, which bf16 may order either way: each moves one row); and its
output must be the reference's token for token, again but for as many
tokens as there are near ties. A dropped or misrouted assignment takes an
eighth of a token's output with it; bf16 rounding moves a token's output by
under a hundredth.
"""
import numpy as np

from perfbench import loader

#: relative difference allowed between the trainer's bf16 loss (cross
#: entropy and both auxiliary terms) and the float32 reference's. Seen on
#: the chip: 5.7e-7 to 2e-5 (PERF.md section 2, PR 26). On the same 2-layer
#: model in the sandbox the reference's loss moves by 1.4e-6 with weights
#: and activations rounded to bf16, 2.1e-5 with the weights alone, **3.3e-4
#: with weights rounded to fp8 (e4m3)**, 1.2e-3 with 7 experts a token,
#: 1.8e-3 without the z-loss and 1.2e-2 without the load-balance term: 1e-4
#: passes the first two and fails the rest. A capacity of 1.25 drops a third
#: of the sequence's assignments and moves it by 2.2e-5: that is what the
#: routing comparison below is for.
LOSS_RTOL = 1e-4


def loss_agrees(got: float, want: float) -> tuple:
    """(relative difference, whether it is within ``LOSS_RTOL``)."""
    rel = abs(got - want) / abs(want)
    return rel, rel <= LOSS_RTOL


#: a token whose expert-layer output differs from the reference's by more
#: than this share of its norm was routed otherwise (one expert of eight
#: differs: 0.1 to 0.4 seen) and not merely rounded (bf16: under 0.01 seen)
TOKEN_RTOL = 0.05


def layer_routing(w: dict, want: dict, top_k: int) -> dict:
    """The program's expert layer on one layer's weights ``w`` and the
    reference's input ``want["x"]``: assignments it lost, rows it moved
    against ``want["rows"]``, tokens whose output is not ``want["y"]``'s
    within ``TOKEN_RTOL``, the fullest expert's load over the mean, and
    whether all of it is within ``want["near_ties"]``."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.moe import dropless_moe

    dt = w["mlp.w_gate"].dtype
    y, _, _, rows = jax.jit(dropless_moe, static_argnums=5)(
        jnp.asarray(want["x"], dt), w["mlp.gate"], w["mlp.w_gate"],
        w["mlp.w_up"], w["mlp.w_down"], top_k)
    rows = np.asarray(rows).astype(np.int64)
    assigned = want["x"].shape[0] * top_k
    err = np.linalg.norm(np.asarray(y, np.float32) - want["y"], axis=-1) \
        / np.linalg.norm(want["y"], axis=-1)
    out = {"dropped": assigned - int(rows.sum()),
           "moved": int(np.abs(rows - want["rows"]).sum()) // 2,
           "off": int((err > TOKEN_RTOL).sum()),
           "load": float(rows.max()) * rows.size / assigned,
           "allowed": want["near_ties"]}
    out["ok"] = out["dropped"] == 0 and out["moved"] <= out["allowed"] \
        and out["off"] <= out["allowed"]
    return out


def step_routing(stats: dict, assigned: int, n_micro: int, want: list) -> dict:
    """What one step routed, from its ``aux_stats``: assignments with no row
    (of ``assigned``, which the step must count too), and rows a micro-batch
    moved against the reference's layers ``want``, summed."""
    rows = np.rint(np.asarray(stats["moe/rows"], np.float64))
    ref_rows = np.sum([r["rows"] for r in want], axis=0)
    return {"dropped": assigned - int(rows.sum()),
            "counted": int(round(float(stats["moe/assigned"]))) == assigned,
            "moved": int(np.abs(rows / n_micro - ref_rows).sum()) // 2,
            "near_ties": int(sum(r["near_ties"] for r in want))}


def check(ctx, tr, opt, work, losses) -> dict:
    import jax

    c = ctx.config
    ref = loader.load_module("references", c["reference"])
    layer_weights = loader.load_module("checks", "gpt_train").layer_weights
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
    other = dict(zip(tr.other_names, tr.other_vals))
    # one micro-batch's tokens: what the trainer routes together
    want = ref.loss_terms(
        layer_weights(tr), other, np.tile(seq, (work["micro"], 1)),
        c["num_attention_heads"], c["num_experts_per_tok"], c["rms_norm_eps"],
        float(c["rope_theta"]), c["router_aux_loss_coef"],
        c["router_z_loss_coef"])
    rel, agrees = loss_agrees(got, want["loss"])
    layers = [layer_routing(w, r, c["num_experts_per_tok"])
              for w, r in zip(layer_weights(tr), want["routing"])]
    # every micro-batch holds ``micro`` copies of the sequence
    step = step_routing(
        stats, rows * work["seq"] * c["num_experts_per_tok"] * len(layers),
        work["n_micro"] * work["micro"], want["routing"])
    ok = finite and falling and agrees and all(r["ok"] for r in layers) \
        and step["dropped"] == 0 and step["counted"]
    return {"ok": ok,
            "note": f"check: losses finite {finite}, falling "
            f"{bool(falling)}; one sequence's loss {got:.5f} by the "
            f"trainer, {want['loss']:.5f} by the float32 reference (cross "
            f"entropy {want['ce']:.5f}, load balance {want['balance']:.5f}"
            f" x {c['router_aux_loss_coef']}, z {want['z']:.5f} x "
            f"{c['router_z_loss_coef']}; rel {rel:.2e}, allowed "
            f"{LOSS_RTOL:.0e}); that step routed: dropped {step['dropped']}"
            f", assignments counted {step['counted']}, rows moved against "
            f"the reference {step['moved']} a sequence (near ties there "
            f"{step['near_ties']}); the program's expert layer on each layer's "
            "weights and the reference's input: " + "; ".join(
                f"dropped {r['dropped']}, rows moved {r['moved']}, tokens "
                f"off {r['off']} (near ties allow {r['allowed']}), load "
                f"max/mean {r['load']:.2f}" for r in layers)}
