"""The page walk of the serving kernels (``ops/paged_attention._walk_pages``,
ISSUE 60), on its own: interpreted, at toy sizes, under a body that only
records what it was handed. Pages of 4 positions, blocks of 2 pages, slots
of 5 pages (two and a half blocks), rows of 0, 1, 8 (one block), 9 (one block
and a position), 17 and 20 (the capacity) positions, under the three forms of
step the kernels give it: a row (``_ragged_kernel``, ``_grouped_kernel``), a
tile of a row's queries (``_latent_kernel``) and a row under a window
(``_grouped_kernel`` with ``window``). What the kernels' own files check a
kernel at a time (tests/test_ragged_kernel.py, test_latent_kernel.py,
test_gqa_pages.py) is checked here once, for the walk."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import paged_attention as pa

PS, BP, NPS = 4, 2, 5
BT, CAP = BP * PS, NPS * PS
NBLK = -(-CAP // BT)
TQ, TILES = 4, 2            # the tile form: two tiles of four queries a row
WINDOW = 6                  # the window form: not whole pages
LENS = [0, 1, 8, 9, 17, 20]


def _kernel(pt_ref, live_ref, b0_ref, pool_hbm, seen_ref, visits_ref, buf,
            sem, slot_ref, *, tiled: bool, windowed: bool, mask: bool):
    """One grid step: the walk, under a body that writes block ``b``'s
    buffer (all of it; ``mask``: zeros from ``left`` on) to ``seen[b]`` and
    counts its call in ``visits[b]``."""
    r = pl.program_id(0)
    if tiled:
        j, tiles = pl.program_id(1), pl.num_programs(1)
        step = (r, j)
        last = jnp.logical_and(r + 1 == pl.num_programs(0), j + 1 == tiles)
        following = lambda: (jnp.where(j + 1 < tiles, r, r + 1),    # noqa
                             jnp.where(j + 1 < tiles, j + 1, 0))
    else:
        step, last = (r,), r + 1 == pl.num_programs(0)
        following = lambda: (r + 1,)                                # noqa
    at = (0,) * len(step)

    def begin(n_live):
        del n_live
        seen_ref[...] = jnp.full_like(seen_ref, -1.0)
        visits_ref[...] = jnp.zeros_like(visits_ref)

        def body(b, slot, left):
            got = buf[slot].reshape(BT)
            if mask:
                got = jnp.where(jnp.arange(BT) < left, got, 0.0)
            seen_ref[at + (b,)] = got
            visits_ref[at + (b,)] = visits_ref[at + (b,)] + 1

        return body

    pa._walk_pages(
        step, following, last, live=lambda *s: live_ref[s], pt_ref=pt_ref,
        page_copies=lambda page, slot, i: [(pool_hbm.at[page],
                                            buf.at[slot, i])],
        bp=BP, ps=PS, sem=sem, slot_ref=slot_ref, begin=begin,
        first_block=(lambda row: b0_ref[row]) if windowed else None)


def _walk(table, live, b0, pool, mask=False):
    """``(seen [steps.., NBLK, BT], visits [steps.., NBLK])`` of one call."""
    live = np.asarray(live, np.int32)
    tiled, windowed = live.ndim == 2, b0 is not None
    b0 = np.zeros(live.shape[0], np.int32) if b0 is None else b0
    steps = live.shape

    def out(*block):
        return pl.BlockSpec((1,) * len(steps) + block,
                            lambda *ids: ids[:len(steps)] + (0,) * len(block))

    return pl.pallas_call(
        functools.partial(_kernel, tiled=tiled, windowed=windowed, mask=mask),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=steps,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[out(NBLK, BT), out(NBLK)],
            scratch_shapes=[pltpu.VMEM((2, BP, PS), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(steps + (NBLK, BT), jnp.float32),
                   jax.ShapeDtypeStruct(steps + (NBLK,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(steps)),
        interpret=pa._interpret(),
    )(jnp.asarray(table, jnp.int32), jnp.asarray(live),
      jnp.asarray(b0, jnp.int32), jnp.asarray(pool, jnp.float32))


def _steps(form, lens):
    """``(live, b0, row of each step)``: what each step of the form's grid
    sees of rows of ``lens`` positions, as the kernels compute it."""
    lens = np.asarray(lens)
    if form == "tile":      # the last min(n, 6) positions are the queries
        tl = np.minimum(lens, 6)
        first = np.arange(TILES) * TQ
        seen = (lens - tl)[:, None] + np.minimum(first + TQ, tl[:, None])
        live = np.where(first[None, :] < tl[:, None], seen, 0)
        return live, None, np.repeat(np.arange(len(lens)), TILES)
    if form == "window":    # decode rows: the query at the last position
        oldest = np.maximum(lens - 1 - (WINDOW - 1), 0)
        b0 = np.minimum(oldest // BT, np.maximum(-(-lens // BT) - 1, 0))
        return lens, b0, np.arange(len(lens))
    return lens, None, np.arange(len(lens))


def _pages(n_rows):
    """A pool whose position ``o`` of page ``p`` holds ``p * PS + o``, and a
    table that gives every row ``NPS`` pages of its own, shuffled: none is
    the null page, so a page the walk should not fetch is one it can be
    caught with."""
    ids = np.random.default_rng(0).permutation(
        np.arange(1, 1 + n_rows * NPS)).reshape(n_rows, NPS)
    return np.arange((1 + n_rows * NPS) * PS,
                     dtype=np.float32).reshape(-1, PS), ids


def _check(form, lens, mask=False):
    live, b0, row_of = _steps(form, lens)
    code, table = _pages(len(lens))
    pool = code
    if mask:    # the live positions alone hold numbers
        pool = np.full_like(code, np.nan)
        for r, n in enumerate(lens):
            at = table[r, np.arange(n) // PS], np.arange(n) % PS
            pool[at] = code[at]
    seen, visits = _walk(table, live, b0, pool, mask)
    seen = np.asarray(seen).reshape(-1, NBLK, BT)
    visits = np.asarray(visits).reshape(-1, NBLK)
    fetched = {0}           # pages a correct walk has fetched so far
    for s, (n, r) in enumerate(zip(live.reshape(-1), row_of)):
        lo = 0 if b0 is None else int(b0[r])
        want = np.zeros(NBLK, np.int32)
        want[lo:-(-n // BT)] = 1
        # every block from the step's first to its last live one once, no
        # other: a step that sees nothing visits none
        np.testing.assert_array_equal(visits[s], want, err_msg=f"step {s}")
        for b in range(NBLK):
            if not want[b]:
                assert (seen[s, b] == -1).all()
                continue
            pages = table[r, b * BP:min((b + 1) * BP, -(-n // PS))]
            fetched |= set(pages.tolist())
            left = min(n - b * BT, BT)
            got = code[pages].reshape(-1)
            if mask:        # (iii) the poison around the live positions
                np.testing.assert_array_equal(seen[s, b, :left], got[:left])
                assert (seen[s, b, left:] == 0).all()
                continue
            # (i) the block's live pages, whole, in their places ...
            np.testing.assert_array_equal(seen[s, b, :len(got)], got,
                                          err_msg=f"step {s} block {b}")
            # ... and nothing in the buffer that no step was to fetch yet:
            # what lies behind them is what an earlier block left there
            rest = seen[s, b][np.isfinite(seen[s, b])]
            assert set((rest // PS).astype(int).tolist()) <= fetched, \
                f"step {s} block {b}: {seen[s, b]}"


FORMS = ["row", "tile", "window"]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("lens", [LENS, LENS[::-1]], ids=["up", "down"])
def test_a_step_visits_its_live_pages_once_and_none_past_them(form, lens):
    _check(form, lens)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("lens", [[0, 9, 20], [17, 9, 0], [8, 0, 0, 17]],
                         ids=["first", "last", "two-in-a-row"])
def test_the_step_after_an_empty_one_gets_its_first_block(form, lens):
    _check(form, lens)


@pytest.mark.parametrize("form", FORMS)
def test_poison_around_the_live_positions_stays_out_of_a_masked_block(form):
    _check(form, LENS, mask=True)
