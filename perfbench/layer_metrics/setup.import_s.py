"""The package's own import: the phase ``setup/import``, from the first
line of ``paddle_tpu/__init__.py`` to its last."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_setup").row(run, "import")
