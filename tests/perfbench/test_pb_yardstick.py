"""The reductions from readings to metrics, on synthetic readings."""
import pytest

from perfbench import yardstick


def even_ticks(n, dt, per_tick, t0=100.0):
    return [(t0 + (i + 1) * dt, (i + 1) * per_tick) for i in range(n)]


@pytest.mark.parametrize("slices", [1, 8, 9])
def test_slice_median_equals_plain_rate_when_ticks_are_even(slices):
    ticks = even_ticks(720, 0.05, 34)
    got = yardstick.slice_median_rate(ticks, 102.0, 132.0, slices)
    assert got == pytest.approx(34 / 0.05, rel=1e-9)
    assert yardstick.window_rate(ticks, 102.0, 132.0) == \
        pytest.approx(34 / 0.05, rel=1e-9)


def test_the_window_rate_counts_a_stall_that_the_slice_median_ignores():
    """``serve_tokens_per_s`` is the window rate: all progress over all
    time. The slice median beside it passes over one spoiled slice."""
    dt, per = 0.05, 34
    ticks, t, total = [], 100.0, 0
    for i in range(800):
        t += dt + (2.0 if i == 300 else 0.0)      # one two-second stall
        total += per
        ticks.append((t, total))
    t_open, t_close = 101.0, 141.0
    median = yardstick.slice_median_rate(ticks, t_open, t_close, 8)
    plain = yardstick.window_rate(ticks, t_open, t_close)
    assert median == pytest.approx(per / dt, rel=1e-6)
    assert plain < 0.96 * per / dt        # the whole-window rate shows it
    rates = yardstick.slice_rates(ticks, t_open, t_close, 8)
    assert sum(r < 0.9 * per / dt for r in rates) == 1


def test_every_tick_of_the_window_lies_in_exactly_one_slice():
    ticks = [(100 + 0.37 * i, 10 * i) for i in range(200)]
    rates = yardstick.slice_rates(ticks, 110.0, 150.0, 8)
    width = 40.0 / 8
    # progress summed over slices is the progress between the first and
    # the last mark: nothing is counted twice, nothing is left out
    marks = [max(t for t, _ in ticks if t <= 110.0 + k * width)
             for k in range(9)]
    summed = sum(r * (b - a) for r, a, b in zip(rates, marks, marks[1:]))
    first = max(p for t, p in ticks if t <= 110.0)
    last = max(p for t, p in ticks if t <= 150.0)
    assert summed == pytest.approx(last - first)


def test_a_slice_without_a_tick_reads_zero_and_bad_windows_raise():
    assert yardstick.slice_rates([(1.0, 5), (9.5, 10)], 0.0, 10.0, 5) == \
        [0.0, 0.0, 0.0, 0.0, pytest.approx(5 / 8.5)]
    with pytest.raises(ValueError):
        yardstick.slice_rates([], 1.0, 1.0, 8)


@pytest.mark.parametrize("p,want", [(50, 5), (90, 9), (95, 10), (100, 10),
                                    (1, 1)])
def test_percentile_is_nearest_rank(p, want):
    assert yardstick.percentile(list(range(10, 0, -1)), p) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        yardstick.percentile([], 90)


def test_flops_per_token_matches_the_programs_own_count():
    """The copy under perfbench/ and GPTConfig.flops_per_token agree today;
    the copy is the yardstick and does not follow the program."""
    from paddle_tpu.models import GPTConfig

    for cfg in (GPTConfig.gpt3_1_3b(), GPTConfig.gpt3_6_7b()):
        widths = {"hidden_size": cfg.hidden_size,
                  "num_layers": cfg.num_layers,
                  "vocab_size": cfg.vocab_size,
                  "ffn_hidden_size": cfg.ffn_hidden_size,
                  "max_seq_len": cfg.max_seq_len}
        assert yardstick.gpt_num_params(widths) == cfg.num_params()
        assert yardstick.gpt_train_flops_per_token(widths, 2048) == \
            cfg.flops_per_token(2048)
    assert yardstick.gpt_train_flops_per_token(widths, 2048) / 1e9 == \
        pytest.approx(43.17, rel=1e-3)   # 6.7B: 6 x 6.66 B + 3.2 G


def test_flash_and_pool_counts():
    ops, nbytes = yardstick.flash_ops_bytes(2, 2048, 16, 128)
    assert ops == 2 * (2 * 2 * 16 * 2048 * 2048 * 128) / 2
    assert nbytes == 4 * 2 * 2048 * 16 * 128 * 2
    bops, bbytes = yardstick.flash_ops_bytes(2, 2048, 16, 128, backward=True)
    assert bops == 2.5 * ops and bbytes == 2 * nbytes
    # the 1.3B serving pool of 12 slots: 2.4 GB for K, as much for V
    assert yardstick.whole_pool_bytes(24, 1537, 16, 16, 128) == \
        24 * 1537 * 16 * 16 * 128 * 2
    assert yardstick.whole_pool_bytes(24, 1537, 16, 16, 128) / 1e9 == \
        pytest.approx(2.417, rel=1e-3)


def test_unknown_device_has_no_peak():
    assert yardstick.chip_peak("TPU v5 lite").bf16_flops == 197e12
    assert yardstick.chip_peak("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(ValueError, match="no published peak"):
        yardstick.chip_peak("cpu")
