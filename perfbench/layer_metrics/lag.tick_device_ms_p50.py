"""Median device time of Laguna's serving tick over the traced runs of the tick
program (``served.tick_device_ms_p50``'s reader, this cell's own entry until a
``benchmark`` PR folds it)."""
from perfbench import loader


def read(run):
    helper = loader.load_module("layer_metrics", "_laguna_trace")
    if helper.parts_ms(run) is None:
        return None
    return loader.load_module("layer_metrics", "_tick").device_ms_p50(run)
