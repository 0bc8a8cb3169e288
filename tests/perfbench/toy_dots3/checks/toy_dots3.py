"""The toy family's check is the shipped one, the same five comparisons,
under limits of the toy's own: it serves bf16 at a width of 32 with 8 keys a
query, where one near-tie the bf16 scores order otherwise moves an eighth of
a query's attention (the published widths' limits are read on the chip)."""
import functools

from perfbench import loader

_real = loader.load_module("checks", "dots3_serve")
sample, CONTROLS = _real.sample, _real.CONTROLS
check = functools.partial(_real.check, limits=(4.0, 1.5, 0.3, 0.3, 0.5))
