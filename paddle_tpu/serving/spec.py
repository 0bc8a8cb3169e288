"""Speculative decoding on the paged serving engine (ROADMAP item 4).

Decode throughput on the unified tick is bounded by one target-model
dispatch per emitted token. Classic speculative decoding amortizes that
cost: a small **draft model** runs ``k`` tokens ahead per resident
slot, then ONE target **verify tick** scores every slot's
``k + 1``-token row through the existing mixed-row ragged program
(``models/gpt.py::gpt_ragged_apply`` with ``spec_k`` — a verify row is
exactly a prefill-chunk-shaped row whose logits are kept at every
position, not just the last).

**Greedy acceptance** takes the longest prefix where draft == target
argmax, plus one correction token; the emitted stream is therefore
always the TARGET's own argmax stream, so greedy speculative output is
**bitwise identical** to non-speculative greedy paged decode (which is
itself bitwise vs dense ``generate()``) — the classic invariant, and
this engine's signature parity-contract style (tests/test_spec_decode.py
pins it across admission orders, prefix-cache hits, COW divergence,
preemption/requeue mid-speculation, and exact-capacity finishes).

**Sampled acceptance** (ISSUE 20) is the rejection rule: accept draft
token ``t`` with probability ``min(1, p_tgt(t)/p_drf(t))``; on the
first rejection resample from the normalized residual
``max(0, p_tgt - p_drf)`` (``ops/decoding.spec_rejection_sample``).
Both distributions are filtered by the SAME per-request
temperature/top-k/top-p before the ratio — the draft tick filters its
own logits per row, the kernel filters the target's — so the marginal
law at every position is EXACTLY the non-speculative sampling law
(``engine._sample_tok``): ``categorical(fold_in(key, pos), lp)``. The
sampled analogue of greedy's bitwise pin is fixed-key stream equality
at both accept-rate extremes (twin draft → always accept → the
accepted token IS the non-spec draw; disjoint-support draft → always
reject → the residual equals ``p_tgt`` elementwise and the correction
IS the non-spec draw).

Two compiled dispatch sites, each tracing exactly once
(``ServingEngine.compiled_sites`` == {draft tick, verify/mixed tick}):

- **Draft tick** (``make_draft_tick``): the draft KV lives on the SAME
  ``PagePool`` allocator as the target (ISSUE 20 — the dense
  ``[L_d, ns, cap+1]`` buffer is gone): per-slot draft page tables
  (``paged_cache.AuxPageTable``) index draft-dtype pools
  ``[L_d, num_pages, page_size, NH_d, D_d]``, so draft and target
  bytes compete in one refcounted economy and the engine's pressure
  ladder can reclaim draft pages before preempting anyone. Pad and
  overflow writes route to page 0 (the null page — the paged analogue
  of the old dense trash column). One fixed-shape program does BOTH
  draft duties per scheduler step: a ``feed`` stage catches slots up
  to the target's accepted frontier, then a ``generate`` stage scans
  the draft steps; each stage sits behind its own ``lax.cond``.
  The sampling build additionally samples each draft token under the
  slot's own params/key (returning the filtered draft distributions
  the rejection kernel needs) and accepts a **chained frontier**: the
  previous verify tick's raw device outputs (``tok_m``, ``acc``) plus
  ``chain_mask``, from which it computes the post-absorb seed
  ``tok_m[s, acc]`` at position ``pos0 + acc + 1`` ON DEVICE — the
  engine dispatches this chained tick BEFORE materializing the verify
  result, hiding the per-tick host sync under the next draft tick's
  execution (the deferred-sync window spec mode used to forfeit). Its
  generate scan runs ``k + 1`` steps: step 0 re-writes the token at
  ``seed_pos - 1`` (heals the full-acceptance case, where draft ``k``
  was emitted but never written; for every other case it is an
  identical rewrite of an already-valid position, routed to the null
  page when not chained).
- **Verify tick** (``make_spec_tick``): the unified mixed-row tick
  widened with a draft section. Flat token layout
  ``[ns last_tok | ns*k drafts | chunks]``; slot rows group as
  ``[ns, 1+k]`` ragged rows (a non-speculating slot rides with
  ``row_len == 1``). Four ``lax.cond`` branches in ONE executable
  extend the decode-only fast path. The greedy build is unchanged;
  the sampling build threads per-request keys/params and the draft
  distributions, runs the rejection kernel in the spec branches and
  the plain per-row sampling law in the no-draft branches.

**Rewind** is what the PR-5 refcount/COW machinery makes safe: the
rejected tail's KV writes land in pages only this slot holds, so the
engine truncates ``pos`` and returns pages past the new length
(``shrink_slot`` on both the target tables and the draft's
``AuxPageTable``); the draft cache needs no repair — its own
speculation wrote the accepted tokens' KV, and the correction token
arrives as the next round's ``gen_tok`` (or the chained seed).
Preemption resets the slot's draft frontier to 0 and returns its draft
pages; the requeued prompt re-feeds chunk-by-chunk.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from ..profiler import recompile as _recompile
from .paged_cache import AuxPageTable

__all__ = ["SpecConfig", "DraftRunner", "make_draft_tick",
           "make_spec_tick"]


@dataclass
class SpecConfig:
    """Speculative-decoding knobs for ``ServingConfig.spec``.

    ``draft_model``: a dense ``GPT`` sharing the target's vocab (and
    ``max_seq_len >= target's``) — typically much smaller; quality only
    affects the accept rate, never correctness (rejected drafts cost a
    wasted verify position, accepted ones skip a target dispatch).
    ``k``: draft tokens speculated per verify tick; each slot's actual
    depth is clamped per tick by its remaining token budget and page
    headroom (down to 0 = a plain decode row).
    ``adaptive`` (ISSUE 15; serving/sched.py::SpecKController): drive
    each slot's depth from an accept-rate EWMA (alpha ``ewma_alpha``)
    instead of always offering the full ``k``.
    ``reprobe_every`` (ISSUE 16 satellite; ISSUE 20 makes it the BASE
    period): a slot stuck at depth 0 re-probes at depth 1, starting
    every this-many draft ticks and backing off multiplicatively on
    consecutive rejected probes (reset on an accepted one). 0 disables.
    ``overlap`` (ISSUE 20, sampling only): dispatch draft tick N+1
    against the pre-absorb frontier (chained on the verify tick's
    device outputs) BEFORE the host materializes the verify result —
    the per-tick sync hides under the next draft tick. Host-side
    reconcile falls back to a re-generate only when the slot's absorb
    diverged from the chain (EOS/finish/preemption)."""

    draft_model: object
    k: int = 4
    adaptive: bool = False
    ewma_alpha: float = 0.5
    reprobe_every: int = 64
    overlap: bool = False


class DraftRunner:
    """Draft-model state + the ONE jitted draft tick.

    Host side: ``len[s]`` is the slot's draft frontier (paged positions
    ``0..len[s]-1`` hold the accepted sequence's KV) and ``aux`` is the
    slot's draft page table on the SHARED pool allocator. Device side:
    the paged draft pools, donated per dispatch. The engine owns
    scheduling (what to feed, who generates) and frontier bookkeeping;
    this class owns the state and the compiled program."""

    def __init__(self, draft_model, num_slots: int, capacity: int,
                 k: int, feed_width: int, pool):
        cfg = draft_model.config
        if not cfg.is_gpt3_block():
            raise NotImplementedError(
                "the draft tick embeds learned positions and keeps its own "
                "pools of num_layers: it runs GPT-3's block only, not RoPE, "
                "sandwich norms or a looped stack")
        self.config = cfg
        self.k = int(k)
        self.capacity = int(capacity)
        self.feed_width = int(feed_width)
        self.pool = pool
        self.aux = AuxPageTable(pool, num_slots)
        self.stacked, self.other = draft_model._decode_state()
        dt = self.other["embeddings.wte.weight"].dtype
        nh = cfg.num_heads
        hd = cfg.hidden_size // nh
        ps = pool.page_size
        shape = (cfg.num_layers, pool.num_pages, ps, nh, hd)
        self.kc = jnp.zeros(shape, dt)
        self.vc = jnp.zeros(shape, dt)
        self.len = np.zeros(num_slots, np.int64)
        self.site = _recompile.unique_site("serving.draft")
        self.tick = jax.jit(
            make_draft_tick(cfg, num_slots, capacity, k, feed_width,
                            self.site, ps),
            donate_argnums=(2, 3))

    def held_tokens(self, slot: int) -> int:
        """Draft positions covered by the slot's held pages."""
        return self.aux.slot_pages(slot) * self.pool.page_size

    def grow_for(self, slot: int, n_tokens: int) -> bool:
        """Best-effort: hold enough draft pages for ``n_tokens``
        positions. False = pool couldn't cover it (the engine then
        clamps or skips speculation — draft growth never escalates)."""
        return self.aux.grow_to(slot, min(int(n_tokens), self.capacity))

    def rewind(self, slot: int, n_tokens: int) -> int:
        """Truncate the draft frontier to ``n_tokens`` and return pages
        past it to the pool (the rejection-rewind path). Returns pages
        freed."""
        self.len[slot] = int(n_tokens)
        return self.aux.shrink_slot(slot,
                                    self.pool.pages_for(int(n_tokens)))

    def release_pages(self, slot: int) -> int:
        """Pressure decay: return ALL of the slot's draft pages. The
        content is gone, so the frontier resets to 0 — a slot whose
        depth recovers re-feeds from scratch. Returns pages freed."""
        self.len[slot] = 0
        return self.aux.release_slot(slot)

    def reset_slot(self, slot: int) -> None:
        """Invalidate the slot's draft cache (admission / finish /
        preemption): frontier to 0, pages back to the pool."""
        self.len[slot] = 0
        self.aux.release_slot(slot)


def _head(x_last, other, wte):
    if "lm_head.weight" in other:
        return x_last @ other["lm_head.weight"]
    return x_last @ wte.T


def _greedy(logits):
    """The repo's one greedy spelling (ops/decoding.greedy_decode /
    engine._sample_tok): argmax of f32 log_softmax."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.argmax(lp, axis=-1).astype(jnp.int32)


def _sample_rows(logits, keys, pos, temps, top_ks, top_ps):
    """The engine's per-row sampling law (``engine._sample_tok``), on
    device: temperature → per-row top-k/top-p → log_softmax →
    ``categorical(fold_in(key, pos))``. Shared by the draft generate
    scan, the verify tick's plain branches, and (via the same ops) the
    rejection kernel — ONE spelling is what makes spec == non-spec."""
    from ..ops.decoding import apply_top_k_top_p_per_row

    lg = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
    lg = apply_top_k_top_p_per_row(lg, top_ks, top_ps)
    lp = jax.nn.log_softmax(lg, axis=-1)
    def one(key, p, row):
        return jax.random.categorical(jax.random.fold_in(key, p), row)

    tok = jax.vmap(one)(keys, pos, lp).astype(jnp.int32)
    return tok, lp


def make_draft_tick(cfg, num_slots: int, capacity: int, k: int,
                    feed_width: int, site: str, page_size: int):
    """Build the draft tick body (jitted by DraftRunner; pools
    donated). The draft KV is PAGED (ISSUE 20): per-layer pools
    ``[num_pages, page_size, NH, D]`` indexed through the slot's draft
    page table ``dtab`` — position ``p`` of slot ``s`` lives at
    ``(dtab[s, p // ps], p % ps)``; pad/overflow writes route to the
    null page 0, and attention gathers the table view
    ``pool[dtab].reshape(ns, -1, NH, D)`` under the causal mask (null
    entries past the frontier are masked, contributing exactly 0).

    Args (fixed-shape; one trace covers every scheduler state):
      stacked/other   draft decode params
      kc/vc           [L, num_pages, ps, NH, D] paged pools
      dtab            [ns, pages_per_slot] int32 draft page tables
      feed_toks       [ns, F] catch-up tokens per slot
      feed_pos0       [ns]    first feed position per slot
      feed_len        [ns]    real feed tokens (0 = nothing to feed)
      gen_tok         [ns]    generation seed token (the slot's last
                              emitted/accepted token)
      gen_pos         [ns]    its position — ``capacity`` for slots
                              not generating (their writes route to
                              the null page and their drafts are
                              garbage the engine never offers)
      sample_args     None for greedy; the sampling build's tuple,
                              below (the build is chosen by which of
                              the two the engine passes, at trace time)
      has_feed        bool    lax.cond fast path: steady-state ticks
                              skip the feed stage's compute entirely
      has_gen         bool    the symmetric fast path: feed-only ticks
                              skip the generate scan

    Greedy returns (kc, vc, drafts [ns, k]).

    The sampling build's ``sample_args`` are per-request ``keys [ns, 2]
    uint32``, ``temps``/``top_ks``/``top_ps`` [ns] and the chain args
    ``chain_tok_m [ns, 1+k]``, ``chain_acc [ns]``, ``chain_pos0 [ns]``,
    ``chain_mask [ns] bool``; chained rows override
    the seed with ``tok_m[s, acc]`` at ``pos0 + acc + 1`` on device
    (the overlap arm feeds the verify tick's un-materialized outputs
    straight in). Its generate scan runs ``k + 1`` steps — step 0
    re-writes position ``seed_pos - 1`` (the full-acceptance heal; an
    identical rewrite otherwise, null-routed when not chained) — and
    it returns (kc, vc, drafts [ns, k], dprobs [ns, k, V]) where
    ``dprobs`` are the FILTERED draft distributions the rejection
    kernel divides by.
    """
    nh = cfg.num_heads
    hd = cfg.hidden_size // nh
    eps = cfg.layer_norm_eps
    msl = cfg.max_seq_len
    vs = cfg.vocab_size
    ns = num_slots
    cap = capacity
    ps = page_size
    f = feed_width

    from ..models.gpt import _ln, gpt_block_body

    def tick(stacked, other, kc, vc, dtab, feed_toks, feed_pos0,
             feed_len, gen_tok, gen_pos, sample_args, has_feed, has_gen):
        _recompile.mark_trace(site, kc, feed_toks, gen_tok)
        sampling = sample_args is not None
        wte = other["embeddings.wte.weight"]
        wpe = other["embeddings.wpe.weight"]
        rows = jnp.arange(ns)
        slen = dtab.shape[1] * ps
        key_pos = jnp.arange(slen)

        def feed(kc, vc):
            # chunk-style parallel catch-up: F tokens per slot in one
            # forward; pad positions (i >= feed_len) write to the null
            # page
            pos = feed_pos0[:, None] + jnp.arange(f)[None, :]  # [ns, F]
            real = jnp.arange(f)[None, :] < feed_len[:, None]
            live = real & (pos >= 0) & (pos < cap)
            pc = jnp.clip(pos, 0, cap - 1)
            pg = jnp.where(live, dtab[rows[:, None], pc // ps], 0)
            off = pc % ps
            x = wte[feed_toks] + wpe[jnp.clip(pos, 0, msl - 1)]

            def block(xc, inp):
                p, kc0, vc0 = inp

                def attend(q, kk, vv):
                    kcl = kc0.at[pg, off].set(kk)
                    vcl = vc0.at[pg, off].set(vv)
                    kv = kcl[dtab].reshape(ns, slen, nh, hd)
                    vw = vcl[dtab].reshape(ns, slen, nh, hd)
                    att = jnp.einsum("btnd,bsnd->bnts", q, kv) / \
                        math.sqrt(hd)
                    mask = key_pos[None, None, None, :] <= \
                        pos[:, None, :, None]
                    att = jnp.where(mask, att, -1e9)
                    w = jax.nn.softmax(att.astype(jnp.float32),
                                       axis=-1).astype(xc.dtype)
                    return jnp.einsum("bnts,bsnd->btnd", w, vw), \
                        (kcl, vcl)

                return gpt_block_body(cfg, xc, p, attend)

            _, (kc, vc) = jax.lax.scan(block, x, (stacked, kc, vc))
            return kc, vc

        kc, vc = jax.lax.cond(has_feed, feed, lambda a, b: (a, b),
                              kc, vc)

        if sampling:
            keys, temps, top_ks, top_ps, ch_tok_m, ch_acc, ch_pos0, \
                ch_mask = sample_args
            acc_c = jnp.clip(ch_acc, 0, k)
            g_tok = jnp.where(ch_mask, ch_tok_m[rows, acc_c], gen_tok)
            g_pos = jnp.where(ch_mask, ch_pos0 + acc_c + 1, gen_pos)
            # full-acceptance heal (step 0 of the scan): the token at
            # seed_pos - 1 — tok_m[acc - 1] for a chained row with
            # acc >= 1; rows with acc == 0 (and non-chained rows) have
            # that position valid already, so their step-0 write is
            # null-routed
            pre_mask = ch_mask & (ch_acc > 0)
            pre_tok = ch_tok_m[rows, jnp.clip(acc_c - 1, 0, k)]
        else:
            g_tok, g_pos = gen_tok, gen_pos
            pre_mask = jnp.zeros((ns,), bool)
            pre_tok = gen_tok
        scan_len = k + 1 if sampling else k

        def gstep(carry, i):
            tok, kc, vc, p = carry
            if sampling:
                # step 0 writes the heal token, step 1 is FORCED to the
                # seed (step 0's sampled output is not the true token
                # at the seed position), later steps chain as usual
                tok = jnp.where(i == 0, pre_tok,
                                jnp.where(i == 1, g_tok, tok))
                live = (p >= 0) & (p < cap) & \
                    jnp.where(i == 0, pre_mask, True)
            else:
                live = (p >= 0) & (p < cap)
            pc = jnp.clip(p, 0, cap - 1)
            pg = jnp.where(live, dtab[rows, pc // ps], 0)
            off = pc % ps
            x = wte[tok[:, None]] + wpe[jnp.clip(p, 0, msl - 1)][:, None]

            def block(xc, inp):
                pp, kc0, vc0 = inp

                def attend(q, kk, vv):
                    kcl = kc0.at[pg, off].set(kk[:, 0])
                    vcl = vc0.at[pg, off].set(vv[:, 0])
                    kv = kcl[dtab].reshape(ns, slen, nh, hd)
                    vw = vcl[dtab].reshape(ns, slen, nh, hd)
                    att = jnp.einsum("btnd,bsnd->bnts", q, kv) / \
                        math.sqrt(hd)
                    mask = key_pos[None, None, None, :] <= \
                        p[:, None, None, None]
                    att = jnp.where(mask, att, -1e9)
                    w = jax.nn.softmax(att.astype(jnp.float32),
                                       axis=-1).astype(xc.dtype)
                    return jnp.einsum("bnts,bsnd->btnd", w, vw), \
                        (kcl, vcl)

                return gpt_block_body(cfg, xc, pp, attend)

            x, (kc, vc) = jax.lax.scan(block, x, (stacked, kc, vc))
            x = _ln(x, other["ln_f.weight"], other["ln_f.bias"], eps)
            lg = _head(x[:, -1], other, wte)
            if sampling:
                # the token emitted after writing position p sits at
                # p + 1 — the same fold the plain tick uses there
                nxt, lp = _sample_rows(lg, keys, p + 1, temps,
                                       top_ks, top_ps)
                return (nxt, kc, vc, p + 1), (nxt, jnp.exp(lp))
            nxt = _greedy(lg)
            return (nxt, kc, vc, p + 1), nxt

        def generate(kc, vc):
            p0 = g_pos - 1 if sampling else g_pos
            (_, kc, vc, _), out = jax.lax.scan(
                gstep, (g_tok, kc, vc, p0),
                jnp.arange(scan_len), length=scan_len)
            if sampling:
                drafts, probs = out
                # step 0 is the heal write; drafts come from steps 1..k
                return (kc, vc, jnp.swapaxes(drafts[1:], 0, 1),
                        jnp.swapaxes(probs[1:], 0, 1))
            return kc, vc, jnp.swapaxes(out, 0, 1)   # [ns, k]

        def skip(kc, vc):
            if sampling:
                return (kc, vc, jnp.zeros((ns, k), jnp.int32),
                        jnp.zeros((ns, k, vs), jnp.float32))
            return kc, vc, jnp.zeros((ns, k), jnp.int32)

        return jax.lax.cond(has_gen, generate, skip, kc, vc)

    return tick


def make_spec_tick(mcfg, num_slots: int, k: int, chunk_width: int,
                   site: str):
    """Build the spec engine's verify/mixed tick body (jitted by the
    engine; pools donated). This IS the unified mixed-row tick with a
    draft section — same site name, same single-trace contract, the
    same ``pools`` and ``fresh`` arguments (``paged_cache.Pools``; the
    draft model's paged cache stays at its own model dtype whatever
    the pools store).

    Flat token layout: ``[ns last_tok | ns*k drafts | npf*w chunks]``.
    ``sample_ix`` is ``[ns * (1+k)]`` in that layout,
    ``reshape(ns, 1+k)``-able: column 0 is each slot's primary
    emission position (its last_tok row — or, for a slot whose final
    prefill chunk rides this tick, the chunk's last real position),
    columns 1..k its draft verify positions. ``n_draft`` [ns] is the
    per-slot speculation depth this tick (0 = plain decode row).

    Four branches, ONE executable (the decode-only fast-path idiom
    squared): with no drafts aboard the program runs the exact
    non-speculative graph (verify-row capacity costs nothing — the
    plain branches compute only the ns primary logits and scatter
    them into the fixed-shape output); with no chunks aboard the
    prefill capacity is skipped as before.

    Returns (pools, tokens [ns, 1+k], accepted [ns]). With
    ``sample_args`` None (greedy) the tokens are the target's greedy
    argmax at every verify position. The sampling build is chosen, at
    trace time, by passing ``sample_args = (keys [ns, 2] uint32,
    sample_pos [ns] (column-0 emission positions),
    temps/top_ks/top_ps [ns], draft_probs [ns, k, V] (the draft tick's
    filtered distributions))``; its spec branches run
    ``ops/decoding.spec_rejection_sample`` and its plain branches the
    per-row sampling law — acceptance must live INSIDE the branches
    there because it consumes the uniform draws.
    """
    ns = num_slots
    w = chunk_width
    base = ns * (1 + k)

    from ..models.gpt import gpt_ragged_apply
    from ..ops.decoding import spec_accept_length, spec_rejection_sample

    def tick(stacked, other, pools, fresh, last_tok, draft_toks, pf_toks,
             tok_pos, tok_limit, row_tab, row_pos0, row_len, sample_ix,
             n_draft, sample_args, has_chunks, has_drafts):
        _recompile.mark_trace(site, pools.k, row_tab, tok_pos, last_tok)
        # recycled pages start their running-max scale at 0 (the
        # engine lists pages allocated since the last dispatch)
        pools = pools.reset_scales(fresh)
        tokens = jnp.concatenate([last_tok, draft_toks, pf_toks])
        # the no-draft branches run the exact non-speculative layout:
        # the draft section sliced out of every metadata vector
        tokens_plain = jnp.concatenate([last_tok, pf_toks])
        pos_plain = jnp.concatenate([tok_pos[:ns], tok_pos[base:]])
        lim_plain = jnp.concatenate([tok_limit[:ns], tok_limit[base:]])
        # spec-layout sample indices remapped to the plain layout:
        # chunk-section indices shift down by the draft section; draft
        # indices (unused there — n_draft is all-zero whenever a plain
        # branch runs) clamp to 0
        is_draft = (sample_ix >= ns) & (sample_ix < base)
        ix_plain = jnp.where(
            sample_ix < ns, sample_ix,
            jnp.where(is_draft, 0, sample_ix - ns * k))
        primary_ix = ix_plain[jnp.arange(ns) * (1 + k)]

        def scatter_primary(tok_ns):
            # fixed-shape output: each slot's primary token lands at
            # its column-0 position; draft columns stay 0 (garbage the
            # host never reads when has_drafts is False)
            out = jnp.zeros((base,), jnp.int32)
            return out.at[jnp.arange(ns) * (1 + k)].set(tok_ns)

        def run(pl_, toks_, pos_, lim_, tab_, p0_, len_, six_, sk):
            # (a verify tick is K and V alone, of a model that is not
            # looped, by the engine's refusals: ``aux`` is empty)
            return gpt_ragged_apply(
                mcfg, stacked, other, pl_, toks_, pos_, lim_, tab_, p0_,
                len_, six_, decode_rows=ns, chunk_width=w, spec_k=sk)[:2]

        if sample_args is not None:
            keys, sample_pos, temps, top_ks, top_ps, draft_probs = \
                sample_args

            def accept(lg):
                tk, acc = spec_rejection_sample(
                    lg.reshape(ns, 1 + k, -1), draft_probs,
                    draft_toks.reshape(ns, k), n_draft, keys,
                    sample_pos, temps, top_ks, top_ps)
                return tk.reshape(base), acc

            def plain(lg):
                tok, _ = _sample_rows(lg, keys, sample_pos, temps,
                                      top_ks, top_ps)
                return scatter_primary(tok), jnp.zeros((ns,), jnp.int32)
        else:
            def accept(lg):
                # acceptance runs OUTSIDE the branches in greedy mode
                # (spec_accept_length is a pure token compare); keep
                # the branch contract uniform anyway
                return _greedy(lg), jnp.zeros((ns,), jnp.int32)

            def plain(lg):
                return scatter_primary(_greedy(lg)), \
                    jnp.zeros((ns,), jnp.int32)

        def spec_mixed(pl_):
            lg, pl_ = run(pl_, tokens, tok_pos, tok_limit, row_tab,
                          row_pos0, row_len, sample_ix, k)
            return accept(lg) + (pl_,)

        def spec_only(pl_):
            lg, pl_ = run(pl_, tokens[:base], tok_pos[:base],
                          tok_limit[:base], row_tab[:ns], row_pos0[:ns],
                          row_len[:ns], sample_ix, k)
            return accept(lg) + (pl_,)

        def plain_mixed(pl_):
            lg, pl_ = run(pl_, tokens_plain, pos_plain, lim_plain,
                          row_tab, row_pos0, row_len, primary_ix, 0)
            return plain(lg) + (pl_,)

        def plain_only(pl_):
            lg, pl_ = run(pl_, tokens_plain[:ns], pos_plain[:ns],
                          lim_plain[:ns], row_tab[:ns], row_pos0[:ns],
                          row_len[:ns], primary_ix, 0)
            return plain(lg) + (pl_,)

        toks, acc, pools = jax.lax.cond(
            has_drafts,
            lambda pl_: jax.lax.cond(has_chunks, spec_mixed,
                                     spec_only, pl_),
            lambda pl_: jax.lax.cond(has_chunks, plain_mixed,
                                     plain_only, pl_),
            pools)
        tok_m = toks.reshape(ns, 1 + k)
        if sample_args is None:
            acc = spec_accept_length(draft_toks.reshape(ns, k),
                                     tok_m[:, :k], n_draft)
        return pools, tok_m, acc

    return tick
