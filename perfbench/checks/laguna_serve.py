"""Are the tokens the engine emitted the model's, through grouped K/V pages of
two kinds (the full layers' and, in a page space that holds only the window,
the windowed layers'), and are the pages themselves and the routing the
model's? Outside the window, at the sizes the window ran.

For a seeded sample of the finished requests whose ticks the engine kept a
record of (``models/laguna.TickRecord``): **the longest, in the cell one of
the 16,288 + 655 positions that are 62 % of the prefill tokens and the only
traffic past 512 pages and past YaRN's original 8,192 positions**, and one
other) the plain reference (``references/laguna.py``) runs one full causal
forward over prompt and output together: float32, no chunks, no cache, no
kernel. It stands beside an engine that fills the chip, so it keeps no
layer's keys and values there and its blocks of queries shrink with the
context (``references/laguna.query_block``). The engine prefilled the prompt
a chunk of 256 a tick and decoded a token a tick.
Logits are compared, not tokens, and then what the pools hold. Both
distances of logits are read **in units of the position's own standard
deviation over the vocabulary** (``sigma``: a seeded model's logits are
narrow):

(a) the logit of an emitted token, at the position that produced it, lies
    within ``MARGIN`` sigma of that position's largest for 99 of 100 emitted
    tokens, and within twice ``MARGIN`` for all but ``TAIL_SHARE`` of them;
(b) the largest logit of each emitting row, which the tick hands out beside
    its token (``TickRecord.top_logits``), lies within ``LOGIT_TOL`` sigma of
    the reference's largest there, root mean square over the request's
    emitted positions;
(c) **the held experts' rows a layer**: of the held experts each emitting
    row used in each sparse layer (``TickRecord.routed_experts``), at most a
    share ``ROUTE_TOL`` differs from the reference's choice at that position
    (bf16 near ties), and the rows ``held_moe`` gave its experts are the rows
    the tick's own routing counts, in every tick (exact);
(d) **the first full layer's pages**: for requests still decoding when the
    run ended (their slots still hold them; the one that holds the most
    positions and one other: the reference runs its first two layers alone
    for these), the slot's K and V rows in
    layer 0, every position the request holds, against the reference's
    rotated keys and values, relative Frobenius error within ``KV_TOL``
    (layer 0's input is the embedding's rows, the same numbers on both
    sides: what differs is bf16's rounding of the projection and the
    rotation: YaRN's table, the partial span and ``attention_factor`` show
    here);
(e) **the first windowed layer's pages**: the same of layer 1, over the
    positions its window still holds, within ``WINDOW_KV_TOL`` (its input
    has passed layer 0 in bf16 on one side and float32 on the other: the
    gate and whatever else moves layer 0's output shows here).

``control`` runs the same comparison against a model that is wrong on
purpose and must come out false by one of the limits: ``"fp8"`` (weights
rounded to e4m3, the nearest precision below the configuration's bf16) and
the reference's own (``references/laguna.CONTROLS``). The window's runs never
pass one.
"""
import numpy as np

from perfbench import loader

#: Each limit lies between two readings on the chip at the published widths
#: (my chip runs, PR 57; PERF.md section 2): what the served bf16 path read
#: over the cell's runs and ``benchmarks/laguna_controls.py``'s served pass
#: (PERF.md section 2 counts the passes; since the review round the longest
#: request compared holds 16,942 positions), and what the controls read from the
#: tree's export under these limits (seed 2147491019: requests of 16,407
#: and 1,229 positions finished, slots still decoding after 16,335 and
#: 2,910).
#:
#: How far below the reference's maximum the emitted token's logit may lie,
#: in sigma, for 99 of 100 emitted tokens. Served: 0.104-0.198 (the worst
#: token 0.21-0.86: one token past twice the margin in two passes, 0.0004
#: and 0.0008 of theirs).
#: softmax_router 0.438, fp8_kv 0.442, no_window 0.469, no_routed_scaling
#: 0.487, fp8 0.919, no_shared 2.14, no_gate 3.51, no_attention_factor 4.94,
#: no_yarn 5.10, full_rotary 6.43.
MARGIN = 0.3
#: Share of the emitted tokens that may fall short by more than twice
#: ``MARGIN``.
TAIL_SHARE = 0.004
#: Root mean square, over a request's emitted positions, of the engine's
#: largest logit less the reference's largest, in sigma. Served: 0.041-0.056.
#: softmax_router 0.131, fp8_kv 0.138, no_window 0.157, no_routed_scaling
#: 0.162, fp8 0.233, the others 0.34-0.44.
LOGIT_TOL = 0.085
#: Share of the held experts the emitting rows used that may differ from the
#: reference's choice. Served: 0.023-0.029 (bf16 near ties). softmax_router
#: 0.064 (the same scores' order: what differs is downstream of the weights),
#: no_routed_scaling 0.086 (three times the served reading, where its
#: shortfall and its largest logit stand 1.6 and 1.9 times over their
#: limits), fp8_kv 0.108, no_window 0.117, fp8 0.221, no_shared 0.289,
#: no_gate 0.569, the rotary controls 0.81-0.96.
ROUTE_TOL = 0.05
#: Relative Frobenius error of a live slot's K and of its V rows in the first
#: full layer (layer 0), the worse. Served: 0.00272-0.00273 in every pass,
#: at 7 k and at 16.8 k positions alike (bf16's rounding of the projection
#: and the rotation). fp8_kv 0.0267, fp8 0.0469, no_attention_factor 0.343,
#: no_yarn 0.755, full_rotary 1.09; the others leave layer 0's keys as served.
KV_TOL = 0.008
#: The same in the first windowed layer (layer 1), within its window.
#: Served: 0.0130-0.0135 in every pass (its input has passed layer 0 in bf16).
#: fp8_kv 0.0917, fp8 0.202, no_gate 0.587, the rotary controls 1.04-1.40; the
#: router's and the window's controls act after it.
WINDOW_KV_TOL = 0.035
#: what sequences are padded to a multiple of (no further than a slot holds),
#: so that the reference compiles for a few lengths
BUCKET = 1024

#: the seeded sample of recorded requests (the longest and another), the
#: rounding to fp8 and the weights cast as they are asked for are the dots3
#: check's; the requests the run's end found decoding (the one that holds
#: the most positions and another) are the hybrid check's
_dots3 = loader.load_module("checks", "dots3_serve")
_hybrid = loader.load_module("checks", "olmo_hybrid_serve")
_fp8, _Cast = _dots3._fp8, _dots3._Cast
sample, still_decoding = _dots3.sample, _hybrid.still_decoding


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
    the tokens request ``i`` emitted, ``record.top_logits(rid)`` and
    ``record.routed_experts(rid)`` what its ticks said of them,
    ``drive.eng.pool.pools`` the caches. ``limits``: ``(MARGIN, LOGIT_TOL,
    ROUTE_TOL, KV_TOL, WINDOW_KV_TOL)`` of a configuration at other widths
    than the published ones (the tests' toy)."""
    margin, logit_tol, route_tol, kv_tol, window_tol = limits or (
        MARGIN, LOGIT_TOL, ROUTE_TOL, KV_TOL, WINDOW_KV_TOL)
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
    held = tuple(c["experts_held"])
    n_layers, ps = c["num_hidden_layers"], c["engine"]["page_size"]
    config = dict(c, num_experts=c["published"]["num_experts"])
    kinds = c["layer_types"][:n_layers]
    full0, window0 = (kinds.index("full_attention"),
                      kinds.index("sliding_attention"))
    bucket = min(BUCKET, ps * c["engine"]["pages_per_slot"])

    def forward(seq, n, keep=()):
        """The reference over ``seq[:n]``; with ``keep``, through the last
        of those layers alone, their keys and values kept."""
        padded = np.zeros(-(-n // bucket) * bucket, np.int32)
        padded[:n] = seq[:n]
        layers = (_Cast(stacked[f"layer{i}"], cast)
                  for i in range(max(keep) + 1 if keep else n_layers))
        return ref.forward(layers, other, padded, config, held=held,
                           control=wrong, keep=keep)

    shorts, rms, lengths, tokens, used_off, used_all = [], [], [], 0, 0, 0
    record = drive.eng.tick_record
    for i in picked:
        prompt = np.asarray(plan["requests"][i]["prompt"])
        out = drive.output(i)
        if len(out) != plan["requests"][i]["max_new"]:
            return {"ok": False, "note": f"check: request {i} emitted "
                    f"{len(out)} of {plan['requests'][i]['max_new']}"}
        n = len(prompt) + len(out) - 1
        lengths.append(n)
        got = forward(np.concatenate([prompt, out[:-1]]), n)
        # position p holds the logits that chose the token at p + 1
        at = np.arange(len(prompt) - 1, n)
        short, top, sigma = ref.shortfall(got["state"][at], other, out)
        shorts.append(short / sigma)
        rid = drive.rid_of[i]
        tops = np.asarray(record.top_logits(rid), np.float64)
        if tops.shape != top.shape:
            return {"ok": False, "note": f"check: request {i} has "
                    f"{tops.shape[0]} top logits for {top.shape[0]} tokens"}
        rms.append(float(np.sqrt(np.mean(np.square((tops - top) / sigma)))))
        mine_r = record.routed_experts(rid)     # [tokens, layers, k]
        for layer, theirs_r in enumerate(got["routed"]):
            for a, b in zip(mine_r[:, layer] - held[0],
                            np.asarray(theirs_r)[at] - held[0]):
                a = set(a[(a >= 0) & (a < held[1])].tolist())
                b = set(b[(b >= 0) & (b < held[1])].tolist())
                used_off += len(a ^ b)
                used_all += len(a) + len(b)
        tokens += len(out)
        del got
    pages, wpages, held_n = 0.0, 0.0, []
    pool = drive.eng.pool
    window = c["sliding_window"]
    for i, slot, n in live:
        prompt = np.asarray(plan["requests"][i]["prompt"])
        got = forward(np.concatenate([prompt, drive.output(i)]), n,
                      keep=(full0, window0))
        held_n.append(n)
        table = np.asarray(pool.tables[slot][:-(-n // ps)])
        k, v = pool.pools.kv.rows_of(0, table)
        pages = max(pages, _rel(k[:n], got["keys"][full0][:n]),
                    _rel(v[:n], got["values"][full0][:n]))
        # the windowed layer's pages that every later query can still see
        lo = -(-max(n - window, 0) // ps)
        wtable = np.asarray(pool.window_tables[slot][lo:-(-n // ps)])
        if not wtable.all():
            return {"ok": False, "note": f"check: slot {slot} holds no "
                    f"windowed page for some of positions {lo * ps}-{n}"}
        k, v = pool.pools.window.rows_of(0, wtable)
        seen = slice(lo * ps, n)
        wpages = max(wpages,
                     _rel(k[:n - lo * ps], got["keys"][window0][seen]),
                     _rel(v[:n - lo * ps], got["values"][window0][seen]))
        del got
    unaccounted = float(drive.reg.counter(
        "serving/tick_stat_sum{stat=held_rows_unaccounted}").value)
    shorts = np.concatenate(shorts)
    worst = float(np.max(shorts))
    p99 = float(np.quantile(shorts, .99))
    tail = float(np.mean(shorts > tail_margin))
    gap = max(rms)
    route = used_off / max(used_all, 1)
    return {"ok": p99 <= margin and tail <= tail_share and gap <= logit_tol
            and route <= route_tol and unaccounted == 0 and pages <= kv_tol
            and wpages <= window_tol,
            "note": f"check{'' if control is None else ' [' + control + ']'}"
            f": {tokens} tokens of {len(picked)} requests of "
            f"{'/'.join(map(str, lengths))} positions against the "
            f"float32 reference, 99th percentile of an emitted token's "
            f"logit's shortfall {p99:.4f} sigma (allowed {margin}), "
            f"{tail:.4f} of them short by over {tail_margin} (allowed "
            f"{tail_share}), worst {worst:.4f}; a request's rms distance "
            f"of the tick's largest logit from the reference's {gap:.4f} "
            f"sigma (allowed {logit_tol}); of the held experts used "
            f"{route:.4f} differ (allowed {route_tol}); held experts' rows "
            f"the ticks' routing does not account for {unaccounted:g} "
            f"(allowed 0); of {len(live)} slots still decoding after "
            f"{'/'.join(map(str, held_n))} tokens, relative error of the "
            f"first full layer's K/V rows {pages:.5f} (allowed {kv_tol}), "
            f"of the first windowed layer's within its window "
            f"{wpages:.5f} (allowed {window_tol})"}
