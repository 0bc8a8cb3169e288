"""Are the tokens the engine emitted the looped model's, through all of its
caches? Outside the window, at the sizes the window ran.

For a seeded sample of finished requests (the longest always among them)
the plain reference (``references/ouro.py``) runs one full causal forward
over prompt and output together: no cache, float32, all four loop steps.
The engine prefilled the prompt in chunks and decoded a token a tick
through 192 caches, one for every (loop step, layer). Two numbers are
compared, each with its limit, and printed beside it:

(a) every emitted token's logit, at the position that produced it, lies
    within ``MARGIN`` of that position's largest: the engine decodes
    greedily, so it emitted its own argmax, and the reference's logit for
    that token may fall short of the reference's maximum only by what bf16
    arithmetic moves a logit;
(b) the engine's mean expected exit step ``sum_t t p_t`` of each sampled
    request (an output of the tick itself, read through
    ``ServingEngine.exit_steps``) is within ``EXIT_TOL`` of the reference's
    over the same positions: the gate sits on the final norm of every loop
    step, so a step that did not run, or ran on another step's cache, moves
    it although no token says so.

``control`` runs the same comparison against a model that is wrong on
purpose and must come out false by one of the limits: ``"fp8"`` (weights
rounded to e4m3), ``"three_steps"`` (three loop steps of four),
``"shared_cache"`` (steps 2-4 read step 1's cache: index ``layer`` in place
of ``step * 48 + layer``), ``"unrotated_keys"`` (keys cached before the
rotation). The window's runs never pass one.
"""
import numpy as np

from perfbench import loader

#: how far below the reference's maximum the emitted token's logit may lie.
#: Read on the chip at the published widths (my chip runs, PR 33; PERF.md
#: sections 4 and 6), worst over the sampled positions (660-1,283 a run):
#: bf16 as served 0.64-1.21 over 32 runs, as many seeds and two ways of
#: drawing the weights; the controls, through ``check(control=...)`` on the
#: final tree (seed 2900000011): unrotated keys 2.96, three loop steps 5.39,
#: step 1's cache at every step 6.40, fp8 (e4m3) weights 6.42 (an earlier
#: tree and seed: 3.87, 4.73, 6.23, 6.24).
#: Logits of the seeded model have a standard deviation of 0.9. The bf16
#: reading is what 192 layer applications and four final norms on seeded
#: weights make of bf16 rounding: the logits' error doubles with the depth
#: (0.011, 0.022, 0.051, 0.116 rms at 2, 6, 12, 24 of the 48 layers;
#: sandbox, PR 33), which is why this limit is not the 1.3B check's 0.1.
MARGIN = 2.0
#: how far a request's mean expected exit step may lie from the
#: reference's over its emitted positions. Read there: bf16 as served
#: 0.002-0.021 over the same 32 runs; three steps 0.120, fp8 weights 0.246,
#: unrotated keys 0.315, step 1's cache 0.760 (earlier: 0.112, 0.360, 0.170,
#: 0.067). Every control fails by both limits.
EXIT_TOL = 0.04
SAMPLE = 4
CONTROLS = (None, "fp8", "three_steps", "shared_cache", "unrotated_keys")


def sample(ctx, plan, finished) -> list:
    """``SAMPLE`` finished requests: the longest (prompt and output) and
    seeded others."""
    def length(i):
        r = plan["requests"][i]
        return len(r["prompt"]) + r["max_new"]

    longest = max(finished, key=length)
    rest = [i for i in finished if i != longest]
    rng = np.random.default_rng([ctx.seed, 1 << 21])
    more = rng.choice(rest, min(SAMPLE - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [int(i) for i in more]


def _fp8(x):
    """``x`` rounded to fp8 (e4m3) and back: the nearest precision below
    the configuration's bf16."""
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) \
        if x.ndim >= 2 else x


def check(ctx, weights, plan, drive, finished, control=None) -> dict:
    """``weights`` is the engine's ``(stacked, other)``; ``drive.output(i)``
    the tokens request ``i`` emitted, ``drive.eng.exit_steps(rid)`` its
    exit statistics."""
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    if not finished:
        return {"ok": False, "note": "check: no request finished"}
    c = ctx.config
    ref = loader.load_module("references", c["reference"])
    picked = sample(ctx, plan, finished)
    cap = c["engine"]["pages_per_slot"] * c["engine"]["page_size"]
    rows = np.zeros((len(picked), cap), np.int32)
    targets, mask = np.zeros_like(rows), np.zeros(rows.shape, bool)
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

    stacked, other = weights
    cast = _fp8 if control == "fp8" else (lambda x: x)
    n_layers = next(iter(stacked.values())).shape[0]

    def layers():
        for i in range(n_layers):
            yield {k: cast(v[i]) for k, v in stacked.items()}

    other = {k: cast(v) for k, v in other.items()}
    steps = c["total_ut_steps"] - (control == "three_steps")
    got = ref.forward(
        layers, other, rows, c["num_attention_heads"], steps,
        float(c["early_exit_threshold"]), c["rms_norm_eps"],
        float(c["rope_theta"]),
        control if control in ("shared_cache", "unrotated_keys") else None)
    short = ref.shortfall(got["state"], other, targets, mask)[mask]
    worst = float(short.max())
    expected = np.asarray(got["expected"])
    gaps, means = [], []
    for r, i in enumerate(picked):
        mine = drive.eng.exit_steps(drive.rid_of[i])[0]
        gaps.append(abs(mine - float(expected[r][mask[r]].mean())))
        means.append(mine)
    gap = max(gaps)
    return {"ok": worst <= MARGIN and gap <= EXIT_TOL,
            "note": f"check{'' if control is None else ' [' + control + ']'}"
            f": {int(mask.sum())} tokens of {len(picked)} requests against "
            f"the float32 reference through {steps} loop steps, worst "
            f"shortfall of an emitted token's logit {worst:.4f} (allowed "
            f"{MARGIN}), 99th percentile "
            f"{float(np.quantile(short, .99)):.4f}; mean expected exit step "
            f"{float(np.mean(means)):.4f}, a request's furthest from the "
            f"reference's {gap:.5f} (allowed {EXIT_TOL})"}
