"""The yardstick: peaks, operation counts and the reductions from readings
to metrics. Copied from the program where it had sound arithmetic, not
imported, so that a change to the program cannot move it.
"""
from __future__ import annotations

import math
import statistics
from typing import NamedTuple, Sequence


class ChipPeak(NamedTuple):
    bf16_flops: float       # dense bf16 FLOP/s of one chip
    hbm_bytes_per_s: float
    hbm_bytes: float
    ici_bits_per_s: float   # chip-to-chip interconnect of one chip
    source: str


#: keyed by ``jax.devices()[0].device_kind``. Copied from
#: paddle_tpu/profiler/peaks.py (bf16) with the memory and interconnect
#: peaks added. A device that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": ChipPeak(
        197e12, 819e9, 16e9, 1600e9,
        "Google Cloud documentation, 'TPU v5e' system architecture page"),
}


def chip_peak(device_kind: str) -> ChipPeak:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peak for device kind "
                         f"{device_kind!r}: add a sourced row to "
                         f"perfbench/yardstick.py (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


# --- operation counts -----------------------------------------------------
def gpt_num_params(c: dict) -> int:
    """Parameters of the dense GPT of ``models/gpt.py`` (tied head), from
    the widths in a configuration file. Copy of ``GPTConfig.num_params``."""
    h, layers, v = c["hidden_size"], c["num_layers"], c["vocab_size"]
    per_block = 4 * h * h + 2 * h * c["ffn_hidden_size"] + 13 * h
    return v * h + c["max_seq_len"] * h + layers * per_block + 2 * h


def gpt_train_flops_per_token(c: dict, seq: int) -> float:
    """Operations the forward and backward passes need for one token:
    6 N for the matrix multiplications and 12 L h s for attention
    (Megatron's count; recomputation is not counted). Copy of
    ``GPTConfig.flops_per_token``."""
    return 6.0 * gpt_num_params(c) + \
        12.0 * c["num_layers"] * c["hidden_size"] * seq


def flash_ops_bytes(batch: int, seq: int, heads: int, head_dim: int,
                    itemsize: int = 2, causal: bool = True,
                    backward: bool = False) -> tuple:
    """(operations, bytes) one call of flash attention needs. Forward:
    QK^T and PV, two matrix multiplications of 2 b h s^2 d each, half of
    it under a causal mask; reads q, k, v and writes o. Backward: five
    such multiplications (recomputed scores, dv, dp, dq, dk); reads
    q, k, v, o, do and writes dq, dk, dv. Sources: Dao et al.,
    FlashAttention (arXiv:2205.14135) section 3, and FlashAttention-2
    (arXiv:2307.08691) section 3.1 for the backward count."""
    mm = 2.0 * batch * heads * seq * seq * head_dim
    if causal:
        mm /= 2.0
    tensor = batch * seq * heads * head_dim * itemsize
    if backward:
        return 5.0 * mm, 8.0 * tensor
    return 2.0 * mm, 4.0 * tensor


def whole_pool_bytes(layers: int, pages: int, page_size: int, heads: int,
                     head_dim: int, itemsize: int = 2) -> int:
    """Bytes of one page pool (K or V) of the serving engine, all layers:
    what one whole-pool copy reads, and writes again."""
    return layers * pages * page_size * heads * head_dim * itemsize


# --- reductions -----------------------------------------------------------
def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p % of
    the sample at or below it. Raises on an empty sample."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return float(xs[rank - 1])


def slice_rates(ticks: Sequence[tuple], t_open: float, t_close: float,
                slices: int) -> list:
    """Progress per second in each of ``slices`` equal consecutive slices
    of the window ``[t_open, t_close]``. ``ticks`` is the log of completed
    ticks, ``(time, cumulative progress)`` in time order. A slice is cut
    on tick completions: it runs from the last tick completed at or
    before its start to the last tick completed at or before its end, so
    that every tick of the window lies in exactly one slice."""
    if slices < 1 or t_close <= t_open:
        raise ValueError("need a window and at least one slice")
    marks = []                     # last tick at or before each boundary
    i, last = 0, None
    for k in range(slices + 1):
        edge = t_open + (t_close - t_open) * k / slices
        while i < len(ticks) and ticks[i][0] <= edge:
            last = ticks[i]
            i += 1
        marks.append(last)
    rates = []
    for a, b in zip(marks, marks[1:]):
        if a is None or b is None or b[0] <= a[0]:
            rates.append(0.0)      # no tick completed in the slice
        else:
            rates.append((b[1] - a[1]) / (b[0] - a[0]))
    return rates


def slice_median_rate(ticks, t_open, t_close, slices: int = 8) -> float:
    """Median of ``slice_rates``: a slice that a stall spoils moves it
    little, and with evenly spaced ticks it equals the plain rate. No
    end-to-end metric for that reason; it stands beside ``window_rate``."""
    return float(statistics.median(slice_rates(ticks, t_open, t_close,
                                               slices)))


def window_rate(ticks, t_open, t_close) -> float:
    """All progress of the window over all its time, cut like one slice:
    from the last tick completed at or before the opening to the last at
    or before the close. ``serve_tokens_per_s``."""
    return slice_rates(ticks, t_open, t_close, 1)[0]
