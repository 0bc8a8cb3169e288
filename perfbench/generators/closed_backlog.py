"""Closed backlog: every request is queued before the window opens, enough
of them that no slot ever waits for one. Lognormal prompt and output
lengths, nothing shared between prompts.

Parameters: ``requests`` (how many are queued); ``cycle`` (how many
distinct sizes: the queue repeats one cycle of that many quantiles, so a
window some cycles long holds the same work wherever the seed turned the
cycle to); ``prompt`` and
``output`` as ``{median, sigma, lo, hi}``; ``warm_in_s`` the engine runs
before the window opens, so that it opens on a steady mix of prefill and
decode; ``slices`` the window is cut into; ``order_seed``.
"""
import numpy as np

from perfbench import draws


def generate(params: dict, seed: int, seconds: float, limits: dict) -> dict:
    total = params["requests"]
    n = params["cycle"]
    p, o = params["prompt"], params["output"]
    order = params["order_seed"]
    prompts = draws.fixed_order(draws.lognormal_quantiles(
        n, p["median"], p["sigma"], p["lo"], p["hi"]), order + 1)
    outputs = draws.fixed_order(draws.lognormal_quantiles(
        n, o["median"], o["sigma"], o["lo"], o["hi"]), order + 2)
    rng = np.random.default_rng(seed)
    turn = int(rng.integers(0, n))
    prompts, outputs = draws.turned(prompts, turn), draws.turned(outputs, turn)
    requests = []
    for k in range(total):
        plen, new = prompts[k % n], outputs[k % n]
        requests.append({"due_s": 0.0,
                         "max_new": min(new, limits["capacity"] - plen),
                         "prompt": draws.tokens(rng, plen,
                                                limits["vocab_size"])})
    return {"mode": "closed", "requests": requests,
            "warm_in_s": params["warm_in_s"], "drain_limit_s": 0.0}
