"""Is what the trainer computes the model? Outside the window.

The losses of the run are finite and fall (or, once the model has reached
the entropy of uniformly random tokens, stay flat: the last three steps'
mean is at most 0.5 % above the first three's). Then one more step with the
learning rate at 0, which leaves the weights as they are, on a batch that
is one seeded sequence in every row: the loss the trainer reports is that
sequence's loss at those weights, and the plain reference computes the same
from the trainer's own weights.
"""
import numpy as np

from perfbench import loader

#: relative difference allowed between the trainer's bf16 loss and the
#: float32 reference's. Seen on the chip: 2.5e-6 to 2.3e-5 over 24 runs of
#: the two cells (PERF.md). On the same 1.3B model in the sandbox, weights,
#: K/V and activations rounded to bf16 move the reference's loss by 1.1e-5
#: and weights rounded to fp8 (e4m3) by 9.6e-4, so 1e-4 passes the first
#: and fails the second. Weights in int8 with a scale a column move it by
#: 1.6e-5: a mean over 2,047 positions cannot tell those from bf16.
LOSS_RTOL = 1e-4


def layer_weights(tr):
    """One dict a layer from the trainer's stacked ``[pp, lps, ...]``."""
    for stage in range(tr.pp):
        for i in range(tr.lps):
            yield {k: np.asarray(v[stage, i]) for k, v in
                   tr.block_vals.items()}


def loss_agrees(got: float, want: float) -> tuple:
    """(relative difference, whether it is within ``LOSS_RTOL``)."""
    rel = abs(got - want) / abs(want)
    return rel, rel <= LOSS_RTOL


def check(ctx, tr, opt, work, losses) -> dict:
    import jax

    ref = loader.load_module("references", ctx.config["reference"])
    finite = bool(np.isfinite(losses).all())
    falling = np.mean(losses[-3:]) <= 1.005 * np.mean(losses[:3])

    rows = work["micro"] * work["n_micro"]
    seq = np.random.default_rng([ctx.seed, 1 << 20]).integers(
        0, ctx.config["vocab_size"], work["seq"], dtype=np.int32)
    lr = opt.get_lr()
    opt.set_lr(0.0)
    try:
        got = float(jax.block_until_ready(tr.step(np.tile(seq, (rows, 1)))))
    finally:
        opt.set_lr(lr)
    other = dict(zip(tr.other_names, tr.other_vals))
    want = ref.next_token_loss(
        ref.logits(layer_weights(tr), other, seq[None],
                   ctx.config["num_heads"], ctx.config["layer_norm_eps"]),
        seq[None])
    rel, agrees = loss_agrees(got, want)
    ok = finite and falling and agrees
    return {"ok": ok, "note": f"check: losses finite {finite}, falling "
            f"{bool(falling)}; one sequence's loss {got:.5f} by the "
            f"trainer, {want:.5f} by the float32 reference (rel "
            f"{rel:.2e}, allowed {LOSS_RTOL:.0e})"}
