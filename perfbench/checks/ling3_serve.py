"""Are the tokens the engine emitted the model's, through a recurrent state a
slot beside latent pages, a router limited by groups and a share of the
experts, and is the state itself the model's? Outside the window, at the
sizes the window ran.

No request of this cell finishes inside a run (outputs of 9.7 k and 15.6 k
tokens): the check reads requests **still decoding** when the run ended.
For ``SAMPLE`` of those whose ticks the engine kept a record of
(``models/ling3.TickRecord``), both of the traffic's two sizes among them,
the plain reference (``references/ling3.py``) runs one full causal forward
over the prompt and the first ``DECODED`` tokens of the output together:
float32, the delta rule token by token, the attention expanded, no
chunking, no cache, no kernel. The engine prefilled the prompt a chunk of
256 a tick from a carried state and decoded a token a tick against it.
Logits are compared, not tokens, then the routing, and then the state:

(a) the logit of an emitted token, at the position that produced it, lies
    within ``MARGIN`` of that position's largest for 99 of 100 emitted
    tokens, and within twice ``MARGIN`` for all but ``TAIL_SHARE`` of them;
(b) the largest logit of each emitting row, which the tick hands out beside
    its token (``TickRecord.top_logits``), lies within ``LOGIT_TOL`` of the
    reference's logit for the emitted token, root mean square over the
    request's compared positions;
(c) the held experts each emitting row used (``TickRecord.
    routed_experts``) are the reference's, but for a share ``ROUTE_TOL`` of
    them (near ties: bf16 scores against float32);
(d) **the MLA layer's own weighting of its keys**: for each emitting row
    and head, what is the head's own of its latent output ``sum_s w(s)
    c_kv(s)`` (the heads' mean, which is the plain mean of the values, taken
    out) under an alternating sign over the latent's channels, times the
    head's gate (``TickRecord.mla_outputs``), against the reference's at
    that position: relative error over the request's compared positions and
    heads within ``MLA_TOL``. One layer of seven whose output is a hundredth
    of the residual stream's size, and most of that the values' mean, moves
    no logit by what bf16 does not: without this reading its rotation and
    its gate could be dropped unseen (my chip runs, PR 49: both controls
    read the served path's logits to the second digit);
(e) the rows ``held_moe`` gave its experts are the rows the tick's own
    routing statistic counts, in every tick of the run
    (``held_rows_unaccounted`` sums to 0: exact);
(f) **the state**: for one of the sampled requests, the one whose slot holds
    the fewest tokens, the reference runs on over **all** it has emitted, and
    the slot's ``S`` in every KDA layer, as the pool holds it after the last
    tick (``TickRecord.stood_at`` says after how many tokens), is compared
    with the reference's, relative Frobenius error: in the first KDA layer
    within ``STATE_TOL`` (its input has passed nothing but the embedding's
    rows, so what differs is what its own arithmetic rounds, and a state
    *stored* in a narrower type shows), in the worst within
    ``STATE_DEEP_TOL`` (the layers below passed in bf16 on one side and
    float32 on the other).

``control`` runs the same comparison against a model that is wrong on
purpose and must come out false by one of the limits: ``"fp8"`` (weights
rounded to e4m3) and the reference's own (``references/ling3.CONTROLS``).
The window's runs never pass one.
"""
import numpy as np

from perfbench import loader, yardstick_ling3

#: Each limit lies between two readings on the chip at the published widths
#: (my chip runs, PR 49; PERF.md section 2): what the served bf16 path read
#: over 30 runs of the cell and five of ``benchmarks/ling_controls.py``, as
#: many seeds, and what the controls read there (that script, seed
#: 2147497001 from the tree's export: four requests of 731 and 1,435 prompt
#: tokens after 539-554 decoded).
#:
#: How far below the reference's maximum the emitted token's logit may lie,
#: for 99 of 100 emitted tokens. Served: 0.27-0.37 (the worst token
#: 0.56-0.82). fp8 1.84, no_routed_scaling 0.73, no_group_limit 0.69,
#: no_expert_bias 0.89, not_renormalised 4.78, head_decay 5.02,
#: unbounded_decay 5.65, conv_history_dropped 6.39 (bf16_state 0.50,
#: no_head_gate 0.36, no_rope 0.30: they fail by the state and by (d)).
MARGIN = 0.6
#: Share of the emitted tokens that may fall short by more than twice
#: ``MARGIN``. Served: 0 in every run. fp8 0.12, the four above 4: 0.9-1.0.
TAIL_SHARE = 0.004
#: Root mean square, over a request's compared positions, of the engine's
#: largest logit less the reference's logit for the emitted token. Served:
#: 0.11-0.135. no_group_limit 0.25, no_routed_scaling 0.27, no_expert_bias
#: 0.32, fp8 0.74, the others 2.6-4.1 (bf16_state 0.19-0.20, no_head_gate
#: 0.14, no_rope 0.11).
LOGIT_TOL = 0.2
#: Share of the held experts used, over all compared positions and expert
#: layers, that may differ from the reference's. Served: 0.068-0.080 (near
#: ties of bf16 scores against float32). bf16_state 0.126-0.135,
#: no_routed_scaling 0.16, no_group_limit 0.22-0.23, no_expert_bias 0.30,
#: fp8 0.38, the others 0.63-0.96 (no_head_gate 0.083-0.088, no_rope 0.069).
ROUTE_TOL = 0.11
#: Relative error of the MLA layer's heads' own weighting of the latents
#: (gated), the worst sampled request. Served: 0.092-0.110. **no_head_gate
#: 0.53-0.56, no_rope 0.75-0.83** (the two controls this limit exists for: every other
#: reading of theirs is the served path's), bf16_state 0.16, no_group_limit
#: 0.20, no_routed_scaling 0.23, no_expert_bias 0.27, fp8 0.56, the others
#: 1.2-1.6.
MLA_TOL = 0.3
#: Relative Frobenius error of a live slot's state in the first KDA layer.
#: Served: 0.00500-0.00508 after 1,275 to 4,628 tokens. **bf16_state
#: 0.0226-0.0227** (the control this limit exists for), fp8 0.094,
#: head_decay 0.99-1.14, conv_history_dropped 1.65, unbounded_decay 2.8-3.1
#: (the router's, the rotation's and the gate's controls leave it at the
#: served 0.00505: those layers come after it).
STATE_TOL = 0.01
#: The same in the worst KDA layer (the sixth: the error grows with depth,
#: 0.005 0.016 0.044 0.077 0.13 0.16). Served: 0.147-0.172. no_group_limit
#: 0.33, no_routed_scaling 0.35, no_expert_bias 0.38-0.39, fp8 0.73, the
#: others 1.25-3.7 (no_head_gate 0.19-0.21, bf16_state 0.25-0.26, which
#: this limit does not tell).
STATE_DEEP_TOL = 0.3
#: requests compared, and the decoded tokens of each that are
SAMPLE = 4
DECODED = 512
#: sequences are padded to a multiple of this many positions, so that the
#: reference compiles for a few lengths and not for every one
BUCKET = 1024

#: the served path and every control (``"fp8"`` is this file's, the others
#: ``references/ling3.CONTROLS``)
CONTROLS = (None, "fp8", "bf16_state", "unbounded_decay", "head_decay",
            "conv_history_dropped", "no_group_limit", "no_expert_bias",
            "not_renormalised", "no_routed_scaling", "no_rope",
            "no_head_gate")

#: the rounding to fp8 and the weights cast as they are asked for are the
#: dots3 check's, which every served family's check can share
_dots3 = loader.load_module("checks", "dots3_serve")
_fp8, _Cast = _dots3._fp8, _dots3._Cast


def still_decoding(ctx, plan, drive, finished, decoded=DECODED) -> list:
    """``(request, slot, tokens its states hold)`` of up to ``SAMPLE``
    recorded requests that the run's end found decoding with over
    ``decoded`` tokens out, the traffic's sizes in turn (seeded within a
    size), the one whose states hold the fewest tokens first."""
    record = drive.eng.tick_record
    done, by_size = set(finished), {}
    for i, rid in sorted(drive.rid_of.items()):
        if i in done or not record.has(rid) or record.stood_at(rid) is None:
            continue
        slot, pos = record.stood_at(rid)
        n_prompt, n_out = len(plan["requests"][i]["prompt"]), \
            len(drive.output(i))
        # the latest token's query stood at ``pos``: prompt and all but the
        # latest output lie at and before it
        if pos + 2 == n_prompt + n_out and n_out > decoded:
            by_size.setdefault(n_prompt, []).append((i, slot, pos + 1))
    rng = np.random.default_rng([ctx.seed, 1 << 22])
    for group in by_size.values():
        rng.shuffle(group)
    picked = []
    while len(picked) < SAMPLE and any(by_size.values()):
        for size in sorted(by_size):
            if by_size[size] and len(picked) < SAMPLE:
                picked.append(by_size[size].pop())
    return sorted(picked, key=lambda x: x[2])


def check(ctx, weights, plan, drive, finished, control=None,
          limits=None, decoded=None) -> dict:
    """``weights`` is the engine's ``(layers, other)``; ``drive.output(i)``
    the tokens request ``i`` has emitted, ``record.top_logits(rid)`` and
    ``record.routed_experts(rid)`` what its ticks said of them,
    ``drive.eng.pool.pools.state_of`` the states. ``limits``: ``(MARGIN,
    LOGIT_TOL, ROUTE_TOL, MLA_TOL, STATE_TOL, STATE_DEEP_TOL)`` of a configuration at
    other widths than the published ones (the tests' toy), ``decoded`` its
    ``DECODED``."""
    margin, logit_tol, route_tol, mla_tol, state_tol, deep_tol = limits or (
        MARGIN, LOGIT_TOL, ROUTE_TOL, MLA_TOL, STATE_TOL, STATE_DEEP_TOL)
    tail_margin, tail_share = 2 * margin, TAIL_SHARE
    c = ctx.config
    ref = loader.load_module("references", c["reference"])
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    n_dec = decoded or DECODED
    live = still_decoding(ctx, plan, drive, finished, n_dec)
    if not live:
        return {"ok": False, "note": "check: no request with a record was "
                f"still decoding after {n_dec} tokens when the run ended"}
    stacked, other = weights
    cast = _fp8 if control == "fp8" else (lambda x: x)
    other = {k: cast(v) for k, v in other.items()}
    wrong = control if control != "fp8" else None
    chunk = drive.eng.prefill_chunk
    held = tuple(c["experts_held"])
    kinds = yardstick_ling3.kinds(c)

    def layers():
        for n, (i, kind) in enumerate(zip(c["layers_held"], kinds)):
            yield kind, i >= c["first_k_dense_replace"], \
                _Cast(stacked[f"layer{n}"], cast)

    def forward(seq, n, prompt_len):
        padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
        padded[:n] = seq[:n]
        return ref.forward(layers(), other, padded, c, held=held, n_live=n,
                           control=wrong, ticks=(prompt_len, chunk))

    record, pools = drive.eng.tick_record, drive.eng.pool.pools
    shorts, rms, mla, tokens, used_off, used_all = [], [], [], 0, 0, 0
    by_layer, held_n = None, 0
    for k, (i, slot, n_state) in enumerate(live):
        prompt = np.asarray(plan["requests"][i]["prompt"])
        out = drive.output(i)
        seq = np.concatenate([prompt, out])
        # the first sampled request's forward runs over all its states hold
        n = n_state if k == 0 else len(prompt) + n_dec - 1
        got = forward(seq, n, len(prompt))
        # position p holds the logits that chose the token at p + 1
        at = np.arange(len(prompt) - 1, len(prompt) + n_dec - 1)
        short, mine = ref.shortfall(got["state"][at], other, out[:n_dec])
        shorts.append(short)
        rid = drive.rid_of[i]
        tops = np.asarray(record.top_logits(rid)[:n_dec], np.float64)
        rms.append(float(np.sqrt(np.mean(np.square(tops - mine)))))
        # the MLA layers' heads' own weighting of the latents, gated: the
        # engine's rows against the reference's positions
        theirs = np.stack([np.asarray(o)[at] for o in got["mla_out"]], 1)
        ours = record.mla_outputs(rid)[:n_dec]       # [tokens, layers, NH]
        mla.append(float(np.linalg.norm(ours - theirs)
                         / max(np.linalg.norm(theirs), 1e-30)))
        # the held experts each emitting row used: the engine's against the
        # reference's
        mine_r = record.routed_experts(rid)[:n_dec]  # [tokens, layers, k]
        for layer, theirs_r in enumerate(got["routed"]):
            for a, b in zip(mine_r[:, layer] - held[0],
                            np.asarray(theirs_r)[at] - got["held_first"]):
                a = set(a[(a >= 0) & (a < held[1])].tolist())
                b = set(b[(b >= 0) & (b < held[1])].tolist())
                used_off += len(a ^ b)
                used_all += len(a) + len(b)
        tokens += n_dec
        if k == 0:
            held_n, errs = n_state, []
            for layer, theirs in enumerate(got["states"]):
                ours = np.asarray(pools.state_of(
                    layer, np.asarray([slot + 1]),
                    c["num_attention_heads"])[0], np.float64)
                theirs = np.asarray(theirs, np.float64)
                errs.append(float(np.linalg.norm(ours - theirs)
                                  / max(np.linalg.norm(theirs), 1e-30)))
            by_layer = errs
        del got
    unaccounted = float(drive.reg.counter(
        "serving/tick_stat_sum{stat=held_rows_unaccounted}").value)
    first, deep = float(by_layer[0]), float(np.max(by_layer))
    shorts = np.concatenate(shorts)
    worst = float(np.max(shorts))
    p99 = float(np.quantile(shorts, .99))
    tail = float(np.mean(shorts > tail_margin))
    gap, said = max(rms), max(mla)
    route = used_off / max(used_all, 1)
    return {"ok": p99 <= margin and tail <= tail_share and gap <= logit_tol
            and route <= route_tol and said <= mla_tol and unaccounted == 0
            and first <= state_tol and deep <= deep_tol,
            "note": f"check{'' if control is None else ' [' + control + ']'}"
            f": {tokens} tokens of {len(live)} requests still decoding "
            f"against the float32 reference, 99th percentile of an emitted "
            f"token's logit's shortfall {p99:.4f} (allowed {margin}), "
            f"{tail:.4f} of them short by over {tail_margin} (allowed "
            f"{tail_share}), worst {worst:.4f}; a request's rms distance "
            f"of the tick's largest logit from the reference's {gap:.4f} "
            f"(allowed {logit_tol}); of the held experts used "
            f"{route:.4f} differ (allowed {route_tol}); the MLA layer's "
            f"heads' own weighting off by {said:.4f} (allowed {mla_tol}); held "
            f"experts' rows "
            f"the ticks' routing does not account for {unaccounted:g} "
            f"(allowed 0); the states of a slot after {held_n} tokens, "
            f"relative error in the first KDA layer {first:.5f} (allowed "
            f"{state_tol}), in the worst {deep:.5f} (allowed {deep_tol}; by "
            f"layer " + " ".join(f"{e:.4f}" for e in by_layer) + ")"}
