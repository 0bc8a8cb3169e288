"""The toy family's check is the shipped one, the same comparisons, under
limits of the toy's own: it serves bf16 at a width of 32, where one near-tie
of the router's bf16 scores moves a logit by what a whole expert adds (the
published widths' limits are read on the chip)."""
import functools

from perfbench import loader

_real = loader.load_module("checks", "deepseek_v2_serve")
sample, CONTROLS = _real.sample, _real.CONTROLS
check = functools.partial(_real.check, limits=(4.0, 1.5, 0.3))
