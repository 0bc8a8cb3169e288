"""Are the tokens the engine emitted the model's, through grouped K/V pages
and a state-space state a slot **in the same layer**, and are the state, the
convolution's history and the pages themselves the model's? Outside the
window, at the sizes the window ran.

For a seeded sample of the finished requests whose ticks the engine kept a
record of (``models/falcon_h1.TickRecord``; the longest always among them)
the plain reference (``references/falcon_h1.py``) runs one full causal forward
over prompt and output together: float32, the rule token by token, no chunks,
no cache, no kernel. The engine prefilled the prompt a chunk of 256 a tick
from a carried state and decoded a token a tick against it. Logits are
compared, not tokens, and then what the pools hold. A seeded model's logits
are a few hundredths wide (``lm_head_multiplier`` 1/128 on a unit-norm
state), so the two distances of logits are read **in units of the
position's own standard deviation over the vocabulary** (``sigma``):

(a) the logit of an emitted token, at the position that produced it, lies
    within ``MARGIN`` sigma of that position's largest for 99 of 100 emitted
    tokens, and within twice ``MARGIN`` for all but ``TAIL_SHARE`` of them;
(b) the largest logit of each emitting row, which the tick hands out beside
    its token (``TickRecord.top_logits``), lies within ``LOGIT_TOL`` sigma of
    the reference's logit for the emitted token, root mean square over the
    request's emitted positions;
(c) **the first layer's state**: for requests still decoding when the run
    ended (their slots still hold them), the slot's ``S`` in layer 0, as the
    pool holds it after the last tick, against the reference's after the same
    tokens (``TickRecord.stood_at`` says how many), relative Frobenius error
    within ``STATE_TOL``. Layer 0's input is the embedding's rows, the same
    numbers on both sides, so what differs is what this layer's own
    arithmetic rounds: a state *stored* in a narrower type, or a step
    *accumulated* in one, shows beside products that *take* one. The deeper
    layers' errors are in the note and held by nothing: their inputs have
    passed the layers below in bf16 on one side and float32 on the other;
(d) **its history**: the slot's three carried positions of ``[x | B | C]``
    in layer 0 against the reference's projections at the last three
    positions, within ``HISTORY_TOL`` (bf16's rounding of the projection);
(e) **its pages**: the slot's K and V rows in layer 0, every position the
    request holds, against the reference's rotated keys and values, within
    ``KV_TOL``.

``control`` runs the same comparison against a model that is wrong on
purpose and must come out false by one of the limits: ``"fp8"`` (weights
rounded to e4m3, the nearest precision below the configuration's bf16) and
the reference's own (``references/falcon_h1.CONTROLS``: a bf16 state, a step
accumulated in bf16, a dropped multiplier in the mixer and one on the keys,
a state not carried, a history or a bias dropped). The window's runs never
pass one.
"""
import numpy as np

from perfbench import loader

#: Each limit lies between two readings on the chip at the published widths
#: (my chip runs, PR 54; PERF.md section 2): what the served bf16 path read
#: over the cell's runs and ``benchmarks/falcon_h1_controls.py``'s (ten passes,
#: ten seeds), and what the controls read there
#: (seed 2147483791: three requests finished, two still decoding after 653 and
#: 535 tokens).
#:
#: How far below the reference's maximum the emitted token's logit may lie,
#: in sigma, for 99 of 100 emitted tokens. Served: 0.0081-0.0211 (the worst
#: token 0.031-0.045). fp8 0.224, no_key_multiplier 0.459,
#: conv_history_dropped 0.587, no_ssm_out_multiplier 3.02, conv_bias_dropped
#: 5.54 (state_not_carried 0.112, bf16_state 0.012 and bf16_step 0.014, which
#: fail by the state).
MARGIN = 0.06
#: Share of the emitted tokens that may fall short by more than twice
#: ``MARGIN``.
TAIL_SHARE = 0.004
#: Root mean square, over a request's emitted positions, of the engine's
#: largest logit less the reference's logit for the emitted token, in sigma.
#: Served: 0.0118-0.0128. fp8 0.086, state_not_carried 0.075,
#: no_key_multiplier 0.176, conv_history_dropped 0.236, no_ssm_out_multiplier
#: 1.41, conv_bias_dropped 3.41.
LOGIT_TOL = 0.04
#: Relative Frobenius error of a live slot's state in the first layer, the
#: worst sampled request. Served: 0.00047-0.00073 (no deeper layer over
#: 0.0010: the state is a float32 sum of outer products of bf16 operands, with
#: no delta term to feed an error back). fp8 0.0026-0.0032, **bf16_step 0.0055-0.026,
#: bf16_state 0.0116-0.041** (the two controls this limit exists for; at the hybrid's
#: 0.008 the first came out correct), conv_history_dropped 0.053,
#: state_not_carried 6.4, conv_bias_dropped 171.
STATE_TOL = 0.002
#: The same of the slot's convolution history in the first layer.
#: Served: 0.00233-0.00239 in every run (bf16's rounding of the projection).
#: fp8 0.047; no other control moves it.
HISTORY_TOL = 0.01
#: The same of the slot's K and of its V rows in the first layer, the worse.
#: Served: 0.00288-0.00291. fp8 0.047, **no_key_multiplier 0.989** (which moves
#: a logit by under half a sigma: attention's way out is scaled by 0.0375, so
#: the pages themselves are compared).
KV_TOL = 0.01
SAMPLE = 2
#: sequences are padded to a multiple of this many positions, so that the
#: reference compiles for a few lengths and not for every one
BUCKET = 704

#: the seeded sample of recorded requests (the longest always among them),
#: the rounding to fp8 and the weights cast as they are asked for are the
#: dots3 check's, which every served family's check can share; the requests
#: the run's end found decoding are the hybrid check's
_dots3 = loader.load_module("checks", "dots3_serve")
sample, _fp8, _Cast = _dots3.sample, _dots3._fp8, _dots3._Cast
still_decoding = loader.load_module(
    "checks", "olmo_hybrid_serve").still_decoding


def controls(c: dict) -> tuple:
    return ("fp8",) + tuple(loader.load_module(
        "references", c["reference"]).CONTROLS[1:])


def _rel(ours, theirs) -> float:
    ours, theirs = (np.asarray(a, np.float64) for a in (ours, theirs))
    return float(np.linalg.norm(ours - theirs)
                 / max(np.linalg.norm(theirs), 1e-30))


def check(ctx, weights, plan, drive, finished, control=None,
          limits=None) -> dict:
    """``weights`` is the engine's ``(layers, other)``; ``drive.output(i)``
    the tokens request ``i`` emitted, ``record.top_logits(rid)`` what its
    ticks said of them, ``drive.eng.pool.pools`` the caches. ``limits``:
    ``(MARGIN, LOGIT_TOL, STATE_TOL, HISTORY_TOL, KV_TOL)`` of a
    configuration at other widths than the published ones (the tests'
    toy)."""
    margin, logit_tol, state_tol, history_tol, kv_tol = limits or (
        MARGIN, LOGIT_TOL, STATE_TOL, HISTORY_TOL, KV_TOL)
    tail_margin, tail_share = 2 * margin, TAIL_SHARE
    c = ctx.config
    if control is not None and control not in controls(c):
        raise ValueError(f"unknown control {control!r}")
    picked = sample(ctx, plan, drive, finished)
    live = still_decoding(ctx, plan, drive, finished)
    if not picked or not live:
        return {"ok": False, "note": "check: no request with a record "
                f"finished ({len(picked)}) or none was still decoding at "
                f"the end ({len(live)})"}
    ref = loader.load_module("references", c["reference"])
    stacked, other = weights
    cast = _fp8 if control == "fp8" else (lambda x: x)
    other = {k: cast(v) for k, v in other.items()}
    wrong = control if control != "fp8" else None
    chunk = drive.eng.prefill_chunk
    heads, ps = c["mamba_n_heads"], c["engine"]["page_size"]

    def layers():
        for i in range(c["num_hidden_layers"]):
            yield _Cast(stacked[f"layer{i}"], cast)

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
        short, mine, sigma = ref.shortfall(got["state"][at], other, c, out)
        shorts.append(short / sigma)
        tops = np.asarray(record.top_logits(drive.rid_of[i]), np.float64)
        if tops.shape != mine.shape:
            return {"ok": False, "note": f"check: request {i} has "
                    f"{tops.shape[0]} top logits for {mine.shape[0]} tokens"}
        rms.append(float(np.sqrt(np.mean(np.square((tops - mine) / sigma)))))
        tokens += len(out)
        del got
    by_layer, held, history, pages = None, [], 0.0, 0.0
    pools = drive.eng.pool.pools
    for i, slot, n in live:
        prompt = np.asarray(plan["requests"][i]["prompt"])
        seq = np.concatenate([prompt, drive.output(i)])
        got = forward(seq, n, len(prompt))
        held.append(n)
        at = np.asarray([slot + 1])
        errs = [_rel(pools.state_of(layer, at, heads)[0], theirs)
                for layer, theirs in enumerate(got["states"])]
        by_layer = errs if by_layer is None else np.maximum(by_layer, errs)
        history = max(history, _rel(pools.conv[0, :, slot + 1],
                                    got["history"][0]))
        table = np.asarray(drive.eng.pool.tables[slot][:-(-n // ps)])
        k, v = pools.kv.rows_of(0, table)
        pages = max(pages, _rel(k[:n], got["keys"][0][:n]),
                    _rel(v[:n], got["values"][0][:n]))
        del got
    first = float(by_layer[0])
    shorts = np.concatenate(shorts)
    worst = float(np.max(shorts))
    p99 = float(np.quantile(shorts, .99))
    tail = float(np.mean(shorts > tail_margin))
    gap = max(rms)
    return {"ok": p99 <= margin and tail <= tail_share and gap <= logit_tol
            and first <= state_tol and history <= history_tol
            and pages <= kv_tol,
            "note": f"check{'' if control is None else ' [' + control + ']'}"
            f": {tokens} tokens of {len(picked)} requests against the "
            f"float32 reference, 99th percentile of an emitted token's "
            f"logit's shortfall {p99:.4f} sigma (allowed {margin}), "
            f"{tail:.4f} of them short by over {tail_margin} (allowed "
            f"{tail_share}), worst {worst:.4f}; a request's rms distance "
            f"of the tick's largest logit from the reference's {gap:.4f} "
            f"sigma (allowed {logit_tol}); of {len(live)} slots still "
            f"decoding after {'/'.join(map(str, held))} tokens, relative "
            f"error of the first layer's state {first:.5f} (allowed "
            f"{state_tol}; by layer "
            + " ".join(f"{e:.4f}" for e in by_layer)
            + f"), of its history {history:.5f} (allowed {history_tol}), "
            f"of its K/V rows {pages:.5f} (allowed {kv_tol})"}
