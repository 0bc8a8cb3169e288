"""The toy family's check is the shipped one, the same comparisons, under
limits of the toy's own: it serves bf16 at a width of 32 with an
initializer_range of 0.2, where bf16 moves a logit, a near tie of the router
and a state by more than at the published widths (whose limits are read on
the chip), and it compares the first 8 decoded tokens of a request."""
import functools

from perfbench import loader

_real = loader.load_module("checks", "ling3_serve")
still_decoding = _real.still_decoding
check = functools.partial(_real.check, limits=(4.0, 1.5, 0.5, 0.5, 0.3, 0.6),
                          decoded=8)
