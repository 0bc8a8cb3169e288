"""Are the tokens the engine emitted the model's, through K/V pages and a
recurrent state a slot, and is the state itself the model's? Outside the
window, at the sizes the window ran.

For a seeded sample of the finished requests whose ticks the engine kept a
record of (``models/olmo_hybrid.TickRecord``; the longest always among them)
the plain reference (``references/olmo_hybrid.py``) runs one full causal
forward over prompt and output together: float32, the delta rule token by
token, no chunking, no cache, no kernel. The engine prefilled the prompt a
chunk of 256 a tick from a carried state and decoded a token a tick against
it. Logits are compared, not tokens, and then the state:

(a) the logit of an emitted token, at the position that produced it, lies
    within ``MARGIN`` of that position's largest for 99 of 100 emitted
    tokens, and within twice ``MARGIN`` for all but ``TAIL_SHARE`` of them
    (the latent checks' form, PERF.md section 6, PR 37);
(b) the largest logit of each emitting row, which the tick hands out beside
    its token (``TickRecord.top_logits``), lies within ``LOGIT_TOL`` of the
    reference's logit for the emitted token, root mean square over the
    request's emitted positions;
(c) **the state**: for requests still decoding when the run ended (their
    slots still hold them), the slot's ``S`` in every linear layer, as the
    pool holds it after the last tick, against the reference's after the
    same tokens (``TickRecord.stood_at`` says how many), relative Frobenius
    error. **In the first linear layer** within ``STATE_TOL``: its input is
    the embedding's rows, the same numbers on both sides, so what differs is
    what this layer's own arithmetic rounds, and a state *stored* in a
    narrower type shows beside products that *take* one. In the worst layer
    within ``STATE_DEEP_TOL``: a deeper layer's input has passed the layers
    below in bf16 on one side and float32 on the other (the error grows with
    depth, PERF.md section 6, PR 44), so this limit only holds a state that
    is not the model's at all.

``control`` runs the same comparison against a model that is wrong on
purpose and must come out false by one of the limits: ``"fp8"`` (weights
rounded to e4m3), ``"bf16_state"`` (the state rounded to bfloat16 after
every token), ``"state_not_carried"`` (zero again at every chunk of the
prompt), ``"no_decay"`` (``g = 0``), ``"beta_not_doubled"``,
``"conv_history_dropped"`` (zeros before a tick's first token), ``"rope"``
(rotary at theta 5e5). The window's runs never pass one.
"""
import numpy as np

from perfbench import loader

#: Each limit lies between two readings on the chip at the published widths
#: (my chip runs, PR 44; PERF.md section 2): what the served bf16 path read
#: over 16 runs of the cell and two of ``benchmarks/olmoh_controls.py``, as
#: many seeds, and what the controls read there (seed 2147483693: three
#: requests finished, three still decoding after 837-842 tokens).
#:
#: How far below the reference's maximum the emitted token's logit may lie,
#: for 99 of 100 emitted tokens. Served: 0.120-0.165 (the worst token
#: 0.167-0.308). rope 1.40, fp8 2.77 (beta_not_doubled 4.78, no_decay 7.13,
#: conv_history_dropped 7.11, state_not_carried 7.56; bf16_state 0.287, which
#: fails by the state).
MARGIN = 0.6
#: Share of the emitted tokens that may fall short by more than twice
#: ``MARGIN``. Served: 0 in every run. rope 0.025, fp8 0.418, the others
#: 0.89-1.0.
TAIL_SHARE = 0.004
#: Root mean square, over a request's emitted positions, of the engine's
#: largest logit less the reference's logit for the emitted token. Served:
#: 0.065-0.071. rope 0.543, fp8 1.31, the others 2.6-4.6 (bf16_state
#: 0.120).
LOGIT_TOL = 0.2
#: Relative Frobenius error of a live slot's state in the first linear
#: layer, the worst sampled request. Served: 0.0045-0.0055. **bf16_state
#: 0.0116** (the control this limit exists for), fp8 0.090, no_decay 0.81,
#: beta_not_doubled 1.00, conv_history_dropped 2.01, state_not_carried 4.03
#: (rope leaves it at the served 0.0046: the full layers come after it).
STATE_TOL = 0.008
#: The same in the worst linear layer. Served: 0.105-0.206 (the twelfth or
#: the eleventh: the error grows with depth). rope 0.63, fp8 1.06, the
#: others 1.0-4.0 (bf16_state 0.232, which this limit does not tell).
STATE_DEEP_TOL = 0.3
SAMPLE = 2
#: sequences are padded to a multiple of this many positions, so that the
#: reference compiles for a few lengths and not for every one
BUCKET = 704
CONTROLS = (None, "fp8", "bf16_state", "state_not_carried", "no_decay",
            "beta_not_doubled", "conv_history_dropped", "rope")

#: the seeded sample of recorded requests (the longest always among them),
#: the rounding to fp8 and the weights cast as they are asked for are the
#: dots3 check's, which every served family's check can share
_dots3 = loader.load_module("checks", "dots3_serve")
sample, _fp8, _Cast = _dots3.sample, _dots3._fp8, _dots3._Cast


def still_decoding(ctx, plan, drive, finished) -> list:
    """``(request, slot, tokens its states hold)`` of up to ``SAMPLE``
    requests that the run's end found decoding (watched or not: where a
    request's latest row stood is kept for every one): the one whose states
    hold the most tokens and seeded others."""
    record = drive.eng.tick_record
    done, live = set(finished), []
    for i, rid in drive.rid_of.items():
        if i in done or record.stood_at(rid) is None:
            continue
        slot, pos = record.stood_at(rid)
        # the latest token's query stood at ``pos``: prompt and all but the
        # latest output lie at and before it
        if pos + 2 == len(plan["requests"][i]["prompt"]) \
                + len(drive.output(i)):
            live.append((i, slot, pos + 1))
    if not live:
        return []
    live.sort(key=lambda x: -x[2])
    rng = np.random.default_rng([ctx.seed, 1 << 22])
    more = rng.choice(len(live) - 1, min(SAMPLE - 1, len(live) - 1),
                      replace=False) + 1 if len(live) > 1 else []
    return [live[0]] + [live[int(j)] for j in more]


def check(ctx, weights, plan, drive, finished, control=None,
          limits=None) -> dict:
    """``weights`` is the engine's ``(layers, other)``; ``drive.output(i)``
    the tokens request ``i`` emitted, ``record.top_logits(rid)`` what its
    ticks said of them, ``drive.eng.pool.pools.state_of`` the states.
    ``limits``: ``(MARGIN, LOGIT_TOL, STATE_TOL, STATE_DEEP_TOL)`` of a
    configuration at other widths than the published ones (the tests'
    toy)."""
    margin, logit_tol, state_tol, deep_tol = limits or (
        MARGIN, LOGIT_TOL, STATE_TOL, STATE_DEEP_TOL)
    tail_margin, tail_share = 2 * margin, TAIL_SHARE
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")
    picked = sample(ctx, plan, drive, finished)
    live = still_decoding(ctx, plan, drive, finished)
    if not picked or not live:
        return {"ok": False, "note": "check: no request with a record "
                f"finished ({len(picked)}) or none was still decoding at "
                f"the end ({len(live)})"}
    c = ctx.config
    ref = loader.load_module("references", c["reference"])
    stacked, other = weights
    cast = _fp8 if control == "fp8" else (lambda x: x)
    other = {k: cast(v) for k, v in other.items()}
    wrong = control if control != "fp8" else None
    chunk = drive.eng.prefill_chunk
    heads = c["linear_num_value_heads"]

    def layers():
        for i in range(c["num_hidden_layers"]):
            yield c["layer_types"][i], _Cast(stacked[f"layer{i}"], cast)

    def forward(seq, n, prompt_len):
        padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
        padded[:n] = seq[:n]
        return ref.forward(layers(), other, padded, c, n_live=n,
                           control=wrong, ticks=(prompt_len, chunk))

    shorts, rms, tokens = [], [], 0
    record = drive.eng.tick_record
    for i in picked:
        prompt = np.asarray(plan["requests"][i]["prompt"])
        out = drive.output(i)
        if len(out) != plan["requests"][i]["max_new"]:
            return {"ok": False, "note": f"check: request {i} emitted "
                    f"{len(out)} of {plan['requests'][i]['max_new']}"}
        n = len(prompt) + len(out) - 1
        got = forward(np.concatenate([prompt, out[:-1]]), n, len(prompt))
        # position p holds the logits that chose the token at p + 1
        at = np.arange(len(prompt) - 1, n)
        short, mine = ref.shortfall(got["state"][at], other, out)
        shorts.append(short)
        tops = np.asarray(record.top_logits(drive.rid_of[i]), np.float64)
        if tops.shape != mine.shape:
            return {"ok": False, "note": f"check: request {i} has "
                    f"{tops.shape[0]} top logits for {mine.shape[0]} tokens"}
        rms.append(float(np.sqrt(np.mean(np.square(tops - mine)))))
        tokens += len(out)
        del got
    by_layer, held = None, []
    pools = drive.eng.pool.pools
    for i, slot, n in live:
        prompt = np.asarray(plan["requests"][i]["prompt"])
        seq = np.concatenate([prompt, drive.output(i)])
        got = forward(seq, n, len(prompt))
        held.append(n)
        errs = []
        for layer, theirs in enumerate(got["states"]):
            ours = np.asarray(pools.state_of(layer, np.asarray([slot + 1]),
                                             heads)[0], np.float64)
            theirs = np.asarray(theirs, np.float64)
            errs.append(float(np.linalg.norm(ours - theirs)
                              / max(np.linalg.norm(theirs), 1e-30)))
        by_layer = errs if by_layer is None else np.maximum(by_layer, errs)
        del got
    first, deep = float(by_layer[0]), float(np.max(by_layer))
    shorts = np.concatenate(shorts)
    worst = float(np.max(shorts))
    p99 = float(np.quantile(shorts, .99))
    tail = float(np.mean(shorts > tail_margin))
    gap = max(rms)
    return {"ok": p99 <= margin and tail <= tail_share and gap <= logit_tol
            and first <= state_tol and deep <= deep_tol,
            "note": f"check{'' if control is None else ' [' + control + ']'}"
            f": {tokens} tokens of {len(picked)} requests against the "
            f"float32 reference, 99th percentile of an emitted token's "
            f"logit's shortfall {p99:.4f} (allowed {margin}), "
            f"{tail:.4f} of them short by over {tail_margin} (allowed "
            f"{tail_share}), worst {worst:.4f}; a request's rms distance "
            f"of the tick's largest logit from the reference's {gap:.4f} "
            f"(allowed {logit_tol}); the states of {len(live)} slots still "
            f"decoding after {'/'.join(map(str, held))} tokens, relative "
            f"error in the first linear layer {first:.5f} (allowed "
            f"{state_tol}), in the worst {deep:.5f} (allowed {deep_tol}; by "
            f"layer " + " ".join(f"{e:.4f}" for e in by_layer) + ")"}
