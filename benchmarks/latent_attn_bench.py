"""One tick's selected latent attention alone, on the chip, at the row shapes
of ``serve-dots3-longdoc-backlog`` (128 heads over latents of 576, pages of
128, a chunk row of 256 and twelve decode rows over 264 pages a slot): the
Pallas kernel against the XLA spelling, each against a float32 softmax under
``selection_mask``, and their times by the context behind the chunk.

    chiprun --timeout 1500 -- python3 benchmarks/latent_attn_bench.py \
        [tile rows [block tokens]] ...

With arguments, the kernel alone at each ``rows:tokens`` pair named (the
module's own constants first). Times are host-clock means of calls that end
in ``block_until_ready``; a microbench, not a benchmark result.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from paddle_tpu.ops import latent_attention as pa               # noqa: E402

NH, W, C, PS, NPS, TOPK = 128, 576, 512, 128, 264, 2048
SLOTS, CHUNK = 12, 256
SCALE = 1.0 / np.sqrt(192.0)


def case(t, pos0, true_len, seed):
    """Device arrays of one call: a pool of two layers, the rows' own pages
    in a shuffled order, queries, and the selection from seeded scores."""
    rng = np.random.default_rng(seed)
    r = len(pos0)
    pages = SLOTS * NPS + 1
    key = jax.random.PRNGKey(seed)
    pool = (jax.random.normal(key, (2, pages, W, PS), jnp.float32)
            ).astype(jnp.bfloat16)
    table = rng.permutation(np.arange(1, pages))[:r * NPS].reshape(r, NPS)
    pos0, true_len = np.asarray(pos0, np.int32), np.asarray(true_len, np.int32)
    table[true_len == 0] = 0
    q = (jax.random.normal(jax.random.fold_in(key, 1), (r, t, NH, W),
                           jnp.float32) * 0.6).astype(jnp.bfloat16)
    cap = NPS * PS
    live = np.where(true_len > 0, np.minimum(pos0 + true_len, cap), 0)
    last = np.minimum(pos0[:, None] + np.arange(t)[None], live[:, None] - 1)
    score = jax.random.normal(jax.random.fold_in(key, 2), (r, t, cap),
                              jnp.float32)
    seen = jnp.arange(cap)[None, None] <= jnp.asarray(last)[..., None]
    score = jnp.where(seen, score, -jnp.inf)
    keys, thr, ties = jax.jit(pa.select_threshold, static_argnums=1)(
        score.reshape(r * t, cap), TOPK)
    return dict(q=q, pool=pool, table=jnp.asarray(table.astype(np.int32)),
                pos0=jnp.asarray(pos0), true_len=jnp.asarray(true_len),
                keys=keys.reshape(r, t, cap), thr=thr.reshape(r, t),
                ties=ties.reshape(r, t), seen=seen)


def attend(impl):
    return jax.jit(lambda a, layer: pa.selected_latent_attention(
        a["q"], a["pool"], layer, a["table"], a["pos0"], a["true_len"],
        a["keys"], a["thr"], a["ties"], C, SCALE, impl=impl))


def dense(a, layer, rows):
    """Float32 softmax of queries ``rows`` (row, query) over whole rows under
    ``selection_mask``."""
    outs = []
    for r, i in rows:
        flat = jnp.swapaxes(a["pool"][layer, a["table"][r]], 1, 2).reshape(
            -1, W).astype(jnp.float32)
        keep = pa.selection_mask(a["keys"][r, i][None], a["thr"][r, i][None],
                                 a["ties"][r, i][None])[0] & a["seen"][r, i]
        s = a["q"][r, i].astype(jnp.float32) @ flat.T * SCALE
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
        outs.append(p @ flat[:, :C])
    return np.asarray(jnp.stack(outs))


def clock(f, *args, n=5):
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main(argv):
    pairs = [tuple(int(x) for x in a.split(":")) for a in argv] or [
        (pa._LATENT_TILE_ROWS, pa._LATENT_BLOCK_TOKENS)]
    compare = not argv
    print(json.dumps({"device": jax.devices()[0].device_kind}))
    layer = jnp.int32(1)
    rng = np.random.default_rng(0)
    calls = {f"chunk behind {p}": ([p], [CHUNK], CHUNK)
             for p in (0, 2048, 8960, 16384, 20480)}
    calls["chunk of 139 behind 12800"] = ([12800], [139], CHUNK)
    lens = [int(x) for x in rng.integers(13000, 21000, 6)] + [0] * 6
    calls["12 decode rows, 6 live of 13-21 k"] = (lens, [int(n > 0) for n in
                                                         lens], 1)
    for name, (pos0, true_len, t) in calls.items():
        a = case(t, pos0, true_len, 7)
        line = {"call": name}
        for rows, tokens in pairs:
            pa._LATENT_TILE_ROWS, pa._LATENT_BLOCK_TOKENS = rows, tokens
            f = attend("pallas")
            line[f"pallas {rows}x{tokens} ms"] = round(clock(f, a, layer), 3)
        if compare:
            got = np.asarray(f(a, layer), np.float32)
            x = attend("xla")
            line["xla ms"] = round(clock(x, a, layer, n=2), 3)
            ref = np.asarray(x(a, layer), np.float32)
            real = [(r, i) for r in range(len(pos0)) for i in
                    sorted({0, true_len[r] // 2, true_len[r] - 1})
                    if true_len[r]]
            want = dense(a, 1, real)
            at = tuple(np.array(real).T)
            for tag, out in (("pallas", got), ("xla", ref)):
                line[f"{tag} err"] = float(
                    np.abs(out[at] - want).max() / np.abs(want).max())
            line["finite"] = bool(np.isfinite(got).all())
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
