"""Traffic is a pure function of the seed, honours its clips, and gives
every seed the same work in another order."""
import numpy as np
import pytest

from perfbench import draws, loader

LIMITS = {"vocab_size": 50304, "capacity": 2048, "max_seq_len": 2048}
SEEDS = (0, 7, 2 ** 31 + 11)


def plan(traffic, seed, seconds=45.0):
    params = loader.load_data("traffic", traffic)
    gen = loader.load_module("generators", params["generator"])
    return params, gen.generate(params, seed, seconds, LIMITS)


def sizes(p):
    return sorted((len(r["prompt"]), r["max_new"]) for r in p["requests"])


@pytest.mark.parametrize("traffic", ["chat-steady", "longprompt-backlog"])
def test_same_seed_same_requests(traffic):
    _, a = plan(traffic, SEEDS[2])
    _, b = plan(traffic, SEEDS[2])
    assert len(a["requests"]) == len(b["requests"]) > 0
    for x, y in zip(a["requests"], b["requests"]):
        assert x["due_s"] == y["due_s"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("traffic", ["chat-steady", "longprompt-backlog"])
def test_clips_and_capacity(traffic):
    params, p = plan(traffic, SEEDS[1])
    pr, out = params["prompt"], params["output"]
    for r in p["requests"]:
        n = len(r["prompt"])
        assert pr["lo"] <= n <= pr["hi"]
        assert 1 <= r["max_new"] <= out["hi"]
        assert n + r["max_new"] <= LIMITS["capacity"]
        assert r["prompt"].dtype == np.int32
        assert 0 <= r["prompt"].min() and \
            r["prompt"].max() < LIMITS["vocab_size"]
    lens = [len(r["prompt"]) for r in p["requests"]]
    assert min(lens) < pr["median"] < max(lens)      # a spread, not a point


def test_backlog_gives_every_seed_the_same_sizes_in_another_order():
    plans = [plan("longprompt-backlog", s)[1] for s in SEEDS]
    assert sizes(plans[0]) == sizes(plans[1]) == sizes(plans[2])
    order = [[len(r["prompt"]) for r in p["requests"]] for p in plans]
    assert order[0] != order[1]
    assert all(r["due_s"] == 0.0 for r in plans[0]["requests"])
    assert not np.array_equal(plans[0]["requests"][0]["prompt"][:8],
                              plans[1]["requests"][0]["prompt"][:8])


@pytest.mark.parametrize("seed", SEEDS)
def test_backlog_repeats_one_cycle_of_sizes(seed):
    """Any stretch one cycle long holds every size once, wherever it
    starts: a window some cycles long holds the same work for every
    seed."""
    params, p = plan("longprompt-backlog", seed)
    n = params["cycle"]
    assert params["requests"] % n == 0 and params["requests"] >= 4 * n
    first = sizes({"requests": p["requests"][:n]})
    assert len(set(first)) == n
    for start in range(1, len(p["requests"]) - n + 1):
        assert sizes({"requests": p["requests"][start:start + n]}) == first


def test_open_loop_window_holds_one_whole_cycle_for_every_seed():
    """Whatever the seed turns the cycle to, the requests due inside a
    window one cycle long are the same set of sizes and gaps."""
    seconds = 45.0
    seen = []
    for s in SEEDS:
        params, p = plan("chat-steady", s, seconds)
        warm = p["warm_in_s"]
        due = [r["due_s"] for r in p["requests"]]
        assert due == sorted(due) and due[0] > 0
        assert due[-1] >= warm + seconds           # arrivals go on after it
        inside = [r for r in p["requests"]
                  if warm <= r["due_s"] < warm + seconds]
        n = round(params["rate_per_s"] * seconds)
        assert abs(len(inside) - n) <= 1
        seen.append(sorted(len(r["prompt"]) for r in inside))
    # at most the request on the window's edge differs
    assert len(set(seen[0]) ^ set(seen[1])) <= 2
    assert sum(seen[0]) == pytest.approx(sum(seen[1]), rel=0.03)


def test_rate_is_the_files_rate():
    params, p = plan("chat-steady", 5, 45.0)
    horizon = p["requests"][-1]["due_s"]
    assert len(p["requests"]) / horizon == \
        pytest.approx(params["rate_per_s"], rel=0.05)


def test_train_batches_are_seeded_and_fresh_each_step():
    params = loader.load_data("traffic", "train-6x2x2048")
    gen = loader.load_module("generators", params["generator"])
    w = gen.generate(params, SEEDS[2], 45.0, LIMITS)
    assert w["tokens_per_step"] == 6 * 2 * 2048
    a, b = w["batch"](0), w["batch"](1)
    assert a.shape == (12, 2048) and a.dtype == np.int32
    assert not np.array_equal(a, b)
    assert np.array_equal(a, gen.generate(params, SEEDS[2], 45.0,
                                          LIMITS)["batch"](0))
    with pytest.raises(ValueError, match="beyond the model"):
        gen.generate(params, 0, 45.0, {**LIMITS, "max_seq_len": 1024})


def test_quantile_draws():
    xs = draws.lognormal_quantiles(101, 128, 0.8, 16, 1024)
    assert xs == sorted(xs) and xs[50] == 128
    assert xs[0] >= 16 and xs[-1] <= 1024
    gaps = draws.exponential_gaps(72, 45.0)
    assert sum(gaps) == pytest.approx(45.0) and min(gaps) > 0
    assert sorted(draws.fixed_order(xs, 3)) == xs
    assert draws.fixed_order(xs, 3) == draws.fixed_order(xs, 3) != xs
    assert draws.turned([1, 2, 3, 4], 6) == [3, 4, 1, 2]


def head_digest(requests) -> str:
    import hashlib

    h = hashlib.sha256()
    for r in requests:
        h.update(np.int64([len(r["prompt"]), r["max_new"]]).tobytes())
        h.update(np.asarray(r["prompt"], np.int32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,digest", [
    (1, "ecb56dd54a3650b056647023979c9d4fd12a5568a67e5f70a0c5cd21974a01f8"),
    (2500000033,
     "6bfe42064e646c8148e69bc2ef584770877ea415be576f8ea612308288819a1c"),
    (2 ** 31 + 7,
     "692b6217cfdf36ca82bc2ecb38a3e74dd775a7f544540b831844fe914694cb9a"),
])
def test_the_deeper_backlog_starts_with_the_50_requests_it_had(seed, digest):
    """PR 30 raised ``requests`` from 50 to 250 so that a faster engine
    does not run dry. The digests are of the 50 requests (lengths,
    ``max_new``, tokens) that PR 29's tree queued, computed on that tree:
    what a window reaches first is what it reached before."""
    params, p = plan("longprompt-backlog", seed)
    assert params["requests"] == 250 == len(p["requests"])
    assert head_digest(p["requests"][:50]) == digest
    # 335 k tokens over the ~64 s a run drives the engine: it runs dry
    # only above ~5,200 tokens/s
    assert sum(len(r["prompt"]) + r["max_new"]
               for r in p["requests"]) == 5 * 67065
