"""Are the tokens the engine emitted the model's, through its latent caches,
its selections and its windows? Outside the window, at the sizes the window
ran.

For a seeded sample of the finished requests whose ticks the engine kept a
record of (``models/dots3.TickRecord``; the longest always among them)
the plain reference (``references/dots3.py``) runs one full causal forward
over prompt and output together: float32, no cache, the selection a plain
``top_k`` over exact scores, attention in the expanded form under a mask,
the held share of the experts passed in. The engine prefilled the prompt in
chunks of two pages and decoded a token a tick, attending in the absorbed
form over latent pages, 2,048 selected positions a query in the full
layers and the window's pages in the sliding layers, whose older pages it
had given back. Six numbers are compared, each with its limit, and
printed beside it:

(a) the logit of an emitted token, at the position that produced it, lies
    within ``MARGIN`` of that position's largest for 99 of 100 emitted
    tokens, and within twice ``MARGIN`` for all but ``TAIL_SHARE`` of them:
    the engine decodes greedily, so it emitted its own argmax, and the
    reference's logit for that token may fall short of the reference's
    maximum only by what bf16 arithmetic moves a logit. The worst token is
    printed and has no limit of its own: bf16 indexer keys order a near-tie
    at the edge of a selection otherwise, seeded weights give the swapped
    key whatever attention weight chance gives it (up to 0.3 of a head's,
    where a trained indexer would have ranked such a key high), and the
    worst of several hundred tokens read 0.95 to 4.15 over 41 runs on the
    chip against fp8's 3.74: no limit fits between, so the tail is held by
    how many tokens it may hold (PERF.md section 6, PR 37);
(b) the largest logit of each emitting row, which the tick hands out beside
    its token (``TickRecord.top_logits``), lies within ``LOGIT_TOL`` of
    the reference's logit for the emitted token, root mean square over the
    request's emitted positions: a token says little about a layer that
    moves every logit a little, this says it;
(c) the positions the rows that emitted a request's first and last token
    attended in the full layers (``TickRecord.selected_sets``: the mask
    their attention applied, made from the threshold, the ties and the
    scores the attention itself takes, an output of the tick) differ from
    the reference's ``top_k`` at the same positions in at most
    ``SELECT_TOL`` of a set, mean over the sets: near-ties, which bf16
    scores order otherwise (the most a set differs is printed beside it);
(d) the held experts every emitting row used (of the 8 the router chose for
    it, those this chip holds, by their place in the held weights; an
    output of the tick, ``TickRecord.routed_experts``) differ from the
    reference's at the same positions in at most ``ROUTE_TOL`` of all of
    them: near-ties again;
(e) in every sliding layer, the log of the sum of an emitting row's
    exponentiated scores, mean over the heads (an output of the tick,
    ``TickRecord.window_lse``), lies within ``WINDOW_TOL`` of the
    reference's at the same position, root mean square over positions and
    layers: it grows with the log of the keys a query sees, so it tells
    the window from a longer or shorter one where random values averaged
    over 513 or over 16,000 keys move a logit no more than bf16 does.

``control`` runs the same comparison against a model that is wrong on
purpose and must come out false by one of the limits: ``"fp8"`` (weights
rounded to e4m3), ``"recent_topk"`` (the last 2,048 positions in place of
the indexer's), ``"window_all"`` (sliding layers see everything),
``"no_gate"``, ``"unscaled_latent"``, ``"other_share"`` (the held weights
taken for experts 32-63), ``"no_select_bias"``. The window's runs never
pass one.
"""
import numpy as np

from perfbench import loader

#: Each limit lies between two readings on the chip at the published widths
#: (my chip runs, PR 37; PERF.md section 6): what the served bf16 path read
#: over 41 runs of the cell and of ``benchmarks/dots3_controls.py``, as many
#: seeds (the last 20 with the selection bias at deviation 0.02, which moved
#: only (d); 13 of them from the final tree), and what the fp8 (e4m3) control read, or the control the
#: number exists for.
#:
#: How far below the reference's maximum the emitted token's logit may lie,
#: for 99 of 100 emitted tokens. Served: 0.55-0.97. fp8: 3.25, 3.42 (no
#: gate 4.65, the last 2,048 keys 8.10, unscaled latents 8.59).
MARGIN = 1.8
#: Share of the emitted tokens that may fall short by more than twice
#: ``MARGIN``: the tail of (a), which the 99th percentile does not hold.
#: Served: 0 in 40 runs and 0.0010 in one (1 token of 1,004, the worst
#: read: 4.15). fp8: 0.0101 (no gate 0.101, the last 2,048 keys 0.848).
TAIL_SHARE = 0.004
#: Root mean square, over a request's emitted positions, of the engine's
#: largest logit less the reference's logit for the emitted token. Served:
#: 0.207-0.291. fp8: 1.26, 1.33 (no gate 2.39).
LOGIT_TOL = 0.55
#: Share of a selected set that may differ from the reference's, mean over
#: the sets compared. Served: 0.041-0.086 (the most one set differed:
#: 0.105-0.304). fp8: 0.263, 0.282 (no gate 0.335, the last 2,048 keys
#: 0.855).
SELECT_TOL = 0.16
#: Share of the held experts used, over all emitted positions and expert
#: layers, that may differ from the reference's. Served: 0.109-0.131 (20
#: runs with the selection bias at deviation 0.02). Without the bias
#: 0.252; fp8 0.463; the other share's weights 0.970.
ROUTE_TOL = 0.18
#: Root mean square, over emitted positions and sliding layers, of the
#: engine's log-sum of exponentiated scores less the reference's. Served:
#: 0.0118-0.0131. Sliding layers that see everything: 3.28, 3.42 (the log
#: of 16,000 / 513 is 3.4); fp8 reads 0.038 and fails by the others.
WINDOW_TOL = 0.2
SAMPLE = 2
#: sequences are padded to a multiple of this many positions, so that the
#: reference compiles for a few lengths and not for every one
BUCKET = 8448
CONTROLS = (None, "fp8", "recent_topk", "window_all", "no_gate",
            "unscaled_latent", "other_share", "no_select_bias")


def sample(ctx, plan, drive, finished) -> list:
    """``SAMPLE`` of the finished requests whose ticks the engine kept a
    record of: the longest (prompt and output) and seeded others."""
    def length(i):
        r = plan["requests"][i]
        return len(r["prompt"]) + r["max_new"]

    record = drive.eng.tick_record
    finished = [i for i in finished if record.has(drive.rid_of[i])]
    if not finished:
        return []
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

    return x.astype(jnp.float8_e4m3fn).astype(x.dtype) if x.ndim >= 2 else x


class _Cast(dict):
    """A layer's weights by name, each passed through ``cast`` when it is
    asked for (a control's fp8 copy of a layer's held experts, 1.5 GB,
    exists only while the reference's FFN half holds it)."""

    def __init__(self, weights: dict, cast):
        super().__init__(weights)
        self.cast = cast

    def __getitem__(self, name):
        return self.cast(super().__getitem__(name))


def check(ctx, weights, plan, drive, finished, control=None,
          limits=None) -> dict:
    """``weights`` is the engine's ``(layers, other)``; ``drive.output(i)``
    the tokens request ``i`` emitted, ``record.top_logits(rid)`` and
    ``record.selected_sets(rid)`` what its ticks said of them.
    ``limits``: ``(MARGIN, LOGIT_TOL, SELECT_TOL, ROUTE_TOL, WINDOW_TOL)``
    of a configuration at other widths than the published ones (the tests'
    toy)."""
    margin, logit_tol, select_tol, route_tol, window_tol = limits or (
        MARGIN, LOGIT_TOL, SELECT_TOL, ROUTE_TOL, WINDOW_TOL)
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
        for i, kind in enumerate(c["layer_types"]):
            yield (kind, i >= c["first_k_dense_replace"],
                   _Cast(stacked[f"layer{i}"], cast))

    held = (c["experts_held_first"], c["n_routed_experts"])
    cap = c["engine"]["pages_per_slot"] * c["engine"]["page_size"]
    shorts, rms, differ, lse_off, tokens = [], [], [], [], 0
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
        got = ref.forward(layers(), other, seq, c, held,
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
        for pos, sets in record.selected_sets(rid):
            for layer, mine_set in enumerate(sets):
                theirs = np.asarray(got["selected"][layer][pos])
                a = set(mine_set.tolist())
                b = set(theirs[theirs >= 0].tolist())
                differ.append(len(a ^ b) / max(2 * len(b), 1))
        # the held experts each emitting row used, by their place in the
        # held weights: the engine's (held from ``held[0]``) against the
        # reference's (from where it took the held weights to start)
        mine_r = record.routed_experts(rid)          # [tokens, layers, k]
        for layer, theirs_r in enumerate(got["routed"]):
            theirs_r = np.asarray(theirs_r)[at] - got["held_first"]
            ours = mine_r[:, layer] - held[0]
            for a, b in zip(ours, theirs_r):
                a = set(a[(a >= 0) & (a < held[1])].tolist())
                b = set(b[(b >= 0) & (b < held[1])].tolist())
                used_off += len(a ^ b)
                used_all += len(a) + len(b)
        theirs_l = np.stack([np.asarray(x)[at] for x in got["window_lse"]], 1)
        lse_off.append(record.window_lse(rid) - theirs_l)
        tokens += len(out)
    shorts = np.concatenate(shorts)
    worst = float(np.max(shorts))
    p99 = float(np.quantile(shorts, .99))
    tail = float(np.mean(shorts > tail_margin))
    gap = max(rms)
    off, off_most = (float(np.mean(differ)), max(differ)) if differ \
        else (1.0, 1.0)
    route = used_off / max(used_all, 1)
    win = float(np.sqrt(np.mean(np.square(np.concatenate(lse_off)))))
    return {"ok": p99 <= margin and tail <= tail_share and gap <= logit_tol
            and off <= select_tol and route <= route_tol
            and win <= window_tol,
            "note": f"check{'' if control is None else ' [' + control + ']'}"
            f": {tokens} tokens of {len(picked)} requests against the "
            f"float32 reference, 99th percentile of an emitted token's "
            f"logit's shortfall {p99:.4f} (allowed {margin}), "
            f"{tail:.4f} of them short by over {tail_margin} (allowed "
            f"{tail_share}), worst {worst:.4f}; a request's rms distance "
            f"of the tick's largest "
            f"logit from the reference's {gap:.4f} (allowed {logit_tol}); "
            f"{len(differ)} selected sets differ from the reference's by "
            f"{off:.4f} in the mean (allowed {select_tol}), {off_most:.4f} "
            f"at most; of {used_all // 2} held experts used {route:.4f} "
            f"differ (allowed {route_tol}); the sliding layers' log-sums of "
            f"scores lie {win:.4f} rms from the reference's (allowed "
            f"{window_tol})"}
