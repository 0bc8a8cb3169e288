"""Device time a training step spends in the forward flash kernel (Pallas calls
named ``flash_fwd``: the forward pass and its recomputation in the backward
pass); mean over chips and traced steps."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.kernel_ms(pt.doc_of(run), pt.FLASH_FWD,
                        run["facts"].get("traced_steps"))
