"""The toy family's check is the shipped one, the same comparisons, under
limits of the toy's own: it serves bf16 at a width of 32 with an
initializer_range of 0.2, where bf16 moves a logit and a key by more than at
the published widths (whose limits are read on the chip)."""
import functools

from perfbench import loader

_real = loader.load_module("checks", "laguna_serve")
sample, controls = _real.sample, _real.controls
check = functools.partial(_real.check, limits=(1.5, 0.75, 0.3, 0.05, 0.2))
