"""Are the tokens the engine emitted the model's? Outside the window.

For a seeded sample of finished requests the plain reference runs one full
forward over prompt and output together, with no cache. Every emitted token
must have, at the position that produced it, a logit within ``MARGIN`` of
that position's largest: the engine decodes greedily, so it emitted its own
argmax, and the reference's logit for that token may fall short of the
reference's maximum only by what bf16 arithmetic moves a logit. A near-tie
between two tokens cannot fail this; a wrong position, a wrong page or a
stale cache entry does.
"""
import numpy as np

from perfbench import loader

#: how far below the reference's maximum the emitted token's logit may lie.
#: Logits of the seeded 1.3B model have a standard deviation of 0.9 and
#: span 7.6 at a position. Seen on the chip: at most 0.047 over 42 runs
#: (PERF.md). On the same model in the sandbox, the greedy token of a
#: forward with weights, K/V and activations rounded to bf16 falls short by
#: at most 0.018, with fp8 (e4m3) weights by 1.85, with int8 weights 0.084,
#: with int8 K/V 0.017, with fp8 K/V 0.046. So 0.1 fails fp8 weights, a
#: wrong position, a wrong page and a stale entry (they miss by the span),
#: and cannot tell int8 weights or 8-bit K/V from bf16: the worst of some
#: hundred positions is too blunt for that (PERF.md, Open questions).
MARGIN = 0.1
SAMPLE = 4


def layer_weights(stacked: dict):
    n = next(iter(stacked.values())).shape[0]
    for i in range(n):
        yield {k: v[i] for k, v in stacked.items()}


def check(ctx, weights, plan, drive, finished) -> dict:
    """``weights`` is the engine's ``(stacked, other)``; ``drive.output(i)``
    the tokens request ``i`` emitted."""
    if not finished:
        return {"ok": False, "note": "check: no request finished"}
    ref = loader.load_module("references", ctx.config["reference"])
    rng = np.random.default_rng([ctx.seed, 1 << 21])
    picked = rng.choice(finished, min(SAMPLE, len(finished)), replace=False)
    cap = ctx.config["engine"]["pages_per_slot"] * \
        ctx.config["engine"]["page_size"]
    rows = np.zeros((len(picked), cap), np.int32)
    targets, mask = np.zeros_like(rows), np.zeros(rows.shape, bool)
    stacked, other = weights
    for r, i in enumerate(picked):
        prompt = plan["requests"][i]["prompt"]
        out = drive.output(i)
        if len(out) != plan["requests"][i]["max_new"]:
            return {"ok": False, "note": f"check: request {i} emitted "
                    f"{len(out)} of {plan['requests'][i]['max_new']}"}
        rows[r, :len(prompt)] = prompt
        rows[r, len(prompt):len(prompt) + len(out) - 1] = out[:-1]
        # position p holds the logits that chose the token at p + 1
        targets[r, len(prompt) - 1:len(prompt) - 1 + len(out)] = out
        mask[r, len(prompt) - 1:len(prompt) - 1 + len(out)] = True
    lg = ref.logits(layer_weights(stacked), other, rows,
                    ctx.config["num_heads"], ctx.config["layer_norm_eps"])
    short = ref.shortfall(lg, targets, mask)[mask]
    worst = float(short.max())
    return {"ok": worst <= MARGIN,
            "note": f"check: {int(mask.sum())} tokens of {len(picked)} "
            f"requests against the float32 reference, worst shortfall of an "
            f"emitted token's logit {worst:.4f} (allowed {MARGIN}), 99th "
            f"percentile {float(np.quantile(short, .99)):.4f}"}
