"""Are the tokens the engine emitted the model's, through its latent cache,
under YaRN and the router's group limit? Outside the window, at the sizes
the window ran.

For a seeded sample of the finished requests whose ticks the engine kept a
record of (``models/deepseek_v2.TickRecord``; the longest always among them)
the plain reference (``references/deepseek_v2.py``) runs one full causal
forward over prompt and output together: float32, no cache, attention in the
expanded form under a mask, ``top_k`` by sort, the held share of the experts
passed in. The engine prefilled the prompt two chunks of 256 a tick and
decoded a token a tick, attending in the absorbed form over latent pages,
every visible position. Logits are compared, not tokens; four numbers, each
with its limit, printed beside it (the percentile form of (a) is the one the
dots3 check arrived at, PERF.md section 6, PR 37: seeded weights leave a
token's worst shortfall to chance, so the tail is held by how many tokens it
may hold):

(a) the logit of an emitted token, at the position that produced it, lies
    within ``MARGIN`` of that position's largest for 99 of 100 emitted
    tokens, and within twice ``MARGIN`` for all but ``TAIL_SHARE`` of them:
    the engine decodes greedily, so it emitted its own argmax, and the
    reference's logit for that token may fall short of the reference's
    maximum only by what bf16 arithmetic moves a logit;
(b) the largest logit of each emitting row, which the tick hands out beside
    its token (``TickRecord.top_logits``), lies within ``LOGIT_TOL`` of the
    reference's logit for the emitted token, root mean square over the
    request's emitted positions: a token says little about a change that
    moves every logit a little (a softmax scale, a routing weight, a shared
    expert's width), this says it;
(c) the held experts every emitting row used (of the 6 the router chose for
    it within its 3 groups, those this chip holds; an output of the tick,
    ``TickRecord.routed_experts``) differ from the reference's at the same
    positions in at most ``ROUTE_TOL`` of all of them: near-ties, which
    bf16 scores order otherwise. A router with no group limit uses held
    experts where the token's groups exclude them.

``control`` runs the same comparison against a model that is wrong on
purpose and must come out false by one of the limits: ``"fp8"`` (weights
rounded to e4m3), ``"no_group_limit"`` (plain top-6 of 160),
``"no_routed_scaling"`` (weights times 1), ``"renormalised"`` (weights
divided by their sum), ``"no_yarn"`` (unscaled frequencies),
``"no_mscale"`` (``scale = 192^-0.5``), ``"one_shared"`` (a shared expert
1,536 wide). The window's runs never pass one.
"""
import numpy as np

from perfbench import loader

#: Each limit lies between two readings on the chip at the published widths
#: (my chip runs, PR 40; PERF.md section 6): what the served bf16 path read
#: over 16 runs of the cell and two of ``benchmarks/dsv2_controls.py``, as many
#: seeds (15 of them from the final tree's export; ``MARGIN`` was 0.9 in the
#: first 13 and was set to 1.0 from their readings), and what the fp8 (e4m3)
#: control read, or the control the number exists for (the controls'
#: readings: that script, seeds 2147483693 and 2147485561, three requests of
#: 4.3-10.3 k).
#:
#: How far below the reference's maximum the emitted token's logit may lie,
#: for 99 of 100 emitted tokens. Served: 0.066-0.517. fp8: 2.03,
#: 1.77 (no_group_limit 1.06, 1.07, which fails by (c); renormalised 2.76,
#: 2.69; no_routed_scaling 3.05, 3.20; one_shared 3.26, 3.29; no_mscale
#: 3.64, 4.01; no_yarn 6.50, 5.99).
MARGIN = 1.0
#: Share of the emitted tokens that may fall short by more than twice
#: ``MARGIN``: the tail of (a), which the 99th percentile does not hold.
#: Served: 0 in every run (the worst token read 0.82-1.68). Over 2.0:
#: renormalised 0.053, no_routed_scaling 0.084, one_shared 0.147, no_mscale
#: 0.259, no_yarn 0.766; fp8 0.0031 (one token of 320; it fails by the
#: others), no_group_limit 0.
TAIL_SHARE = 0.004
#: Root mean square, over a request's emitted positions, of the engine's
#: largest logit less the reference's logit for the emitted token. Served:
#: 0.073-0.149. fp8: 0.525, 0.552 (no_group_limit 0.361, 0.354; renormalised
#: 0.781, 0.840; no_routed_scaling 0.953, 1.005; one_shared 1.48, 1.46;
#: no_mscale 1.71, 1.82; no_yarn 3.60, 3.71).
LOGIT_TOL = 0.28
#: Share of the held experts used, over all emitted positions and expert
#: layers, that may differ from the reference's. Served: 0.018-0.033.
#: no_group_limit: 0.297, 0.296; fp8 0.265, 0.266 (renormalised, whose
#: choices are the model's, 0.174, 0.192: a different weight moves the next
#: layer's scores).
ROUTE_TOL = 0.1
#: sequences are padded to a multiple of this many positions, so that the
#: reference compiles for a few lengths and not for every one
BUCKET = 2816
CONTROLS = (None, "fp8", "no_group_limit", "no_routed_scaling",
            "renormalised", "no_yarn", "no_mscale", "one_shared")


#: the seeded sample of recorded requests (the longest always among them),
#: the rounding to fp8 and the weights cast as they are asked for are the
#: dots3 check's, which every latent-attention family's check can share
_dots3 = loader.load_module("checks", "dots3_serve")
sample, _fp8, _Cast = _dots3.sample, _dots3._fp8, _dots3._Cast
SAMPLE = _dots3.SAMPLE


def check(ctx, weights, plan, drive, finished, control=None,
          limits=None) -> dict:
    """``weights`` is the engine's ``(layers, other)``; ``drive.output(i)``
    the tokens request ``i`` emitted, ``record.top_logits(rid)`` and
    ``record.routed_experts(rid)`` what its ticks said of them.
    ``limits``: ``(MARGIN, LOGIT_TOL, ROUTE_TOL)`` of a configuration at
    other widths than the published ones (the tests' toy)."""
    margin, logit_tol, route_tol = limits or (MARGIN, LOGIT_TOL, ROUTE_TOL)
    # the tail's threshold is twice the margin at any widths
    tail_margin, tail_share = 2 * margin, TAIL_SHARE
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    picked = sample(ctx, plan, drive, finished)
    if not picked:
        return {"ok": False,
                "note": "check: no request with a record finished"}
    c = ctx.config
    ref = loader.load_module("references", c["reference"])
    stacked, other = weights
    cast = _fp8 if control == "fp8" else (lambda x: x)
    other = {k: cast(v) for k, v in other.items()}

    def layers():
        for i in range(c["num_hidden_layers"]):
            yield (i >= c["first_k_dense_replace"],
                   _Cast(stacked[f"layer{i}"], cast))

    held = (c["experts_held_first"], c["n_routed_experts"])
    # the reference reads the router's width under the published key
    sizes = dict(c, n_routed_experts=c["published"]["n_routed_experts"])
    cap = c["engine"]["pages_per_slot"] * c["engine"]["page_size"]
    shorts, rms, tokens = [], [], 0
    used_off = used_all = 0
    record = drive.eng.tick_record
    for i in picked:
        prompt = np.asarray(plan["requests"][i]["prompt"])
        out = drive.output(i)
        rid = drive.rid_of[i]
        if len(out) != plan["requests"][i]["max_new"]:
            return {"ok": False, "note": f"check: request {i} emitted "
                    f"{len(out)} of {plan['requests'][i]['max_new']}"}
        n = len(prompt) + len(out) - 1
        seq = np.zeros(min(-(-n // BUCKET) * BUCKET, cap), np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):n] = out[:-1]
        got = ref.forward(layers(), other, seq, sizes, held,
                          control if control != "fp8" else None)
        # position p holds the logits that chose the token at p + 1
        at = np.arange(len(prompt) - 1, n)
        short, mine = ref.shortfall(got["state"][at], other, out)
        shorts.append(short)
        tops = np.asarray(record.top_logits(rid), np.float64)
        if tops.shape != mine.shape:
            return {"ok": False, "note": f"check: request {i} has "
                    f"{tops.shape[0]} top logits for {mine.shape[0]} tokens"}
        rms.append(float(np.sqrt(np.mean(np.square(tops - mine)))))
        # the held experts each emitting row used, by their place in the
        # held weights: the engine's against the reference's
        mine_r = record.routed_experts(rid)          # [tokens, layers, k]
        for layer, theirs_r in enumerate(got["routed"]):
            theirs_r = np.asarray(theirs_r)[at] - got["held_first"]
            ours = mine_r[:, layer] - held[0]
            for a, b in zip(ours, theirs_r):
                a = set(a[(a >= 0) & (a < held[1])].tolist())
                b = set(b[(b >= 0) & (b < held[1])].tolist())
                used_off += len(a ^ b)
                used_all += len(a) + len(b)
        tokens += len(out)
        del got
    shorts = np.concatenate(shorts)
    worst = float(np.max(shorts))
    p99 = float(np.quantile(shorts, .99))
    tail = float(np.mean(shorts > tail_margin))
    gap = max(rms)
    route = used_off / max(used_all, 1)
    return {"ok": p99 <= margin and tail <= tail_share and gap <= logit_tol
            and route <= route_tol,
            "note": f"check{'' if control is None else ' [' + control + ']'}"
            f": {tokens} tokens of {len(picked)} requests against the "
            f"float32 reference, 99th percentile of an emitted token's "
            f"logit's shortfall {p99:.4f} (allowed {margin}), "
            f"{tail:.4f} of them short by over {tail_margin} (allowed "
            f"{tail_share}), worst {worst:.4f}; a request's rms distance "
            f"of the tick's largest logit from the reference's {gap:.4f} "
            f"(allowed {logit_tol}); of {used_all // 2} held experts used "
            f"{route:.4f} differ (allowed {route_tol})"}
