"""Open loop: requests arrive on a schedule whether or not earlier ones have
finished. Poisson arrivals at a fixed rate, lognormal prompt and output
lengths, nothing shared between prompts.

Parameters: ``rate_per_s``; ``prompt`` and ``output`` as ``{median, sigma,
lo, hi}``; ``warm_in_s`` of traffic before the window and ``cool_s`` after
it (arrivals go on while the window's requests finish); ``drain_limit_s``
after which an unfinished request has failed; ``order_seed``.
"""
import numpy as np

from perfbench import draws


def generate(params: dict, seed: int, seconds: float, limits: dict) -> dict:
    n = max(1, round(params["rate_per_s"] * seconds))
    p, o = params["prompt"], params["output"]
    order = params["order_seed"]
    gaps = draws.fixed_order(draws.exponential_gaps(n, seconds), order)
    prompts = draws.fixed_order(draws.lognormal_quantiles(
        n, p["median"], p["sigma"], p["lo"], p["hi"]), order + 1)
    outputs = draws.fixed_order(draws.lognormal_quantiles(
        n, o["median"], o["sigma"], o["lo"], o["hi"]), order + 2)
    rng = np.random.default_rng(seed)
    turn = int(rng.integers(0, n))
    gaps, prompts, outputs = (draws.turned(x, turn)
                              for x in (gaps, prompts, outputs))
    horizon = params["warm_in_s"] + seconds + params["cool_s"]
    requests, t, i = [], 0.0, 0
    while True:
        t += gaps[i % n]
        if t >= horizon:
            break
        plen = prompts[i % n]
        new = min(outputs[i % n], limits["capacity"] - plen)
        requests.append({"due_s": t, "max_new": new,
                         "prompt": draws.tokens(rng, plen,
                                                limits["vocab_size"])})
        i += 1
    return {"mode": "open", "requests": requests,
            "warm_in_s": params["warm_in_s"],
            "drain_limit_s": params["drain_limit_s"]}
