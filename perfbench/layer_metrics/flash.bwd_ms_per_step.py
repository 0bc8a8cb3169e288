"""Device time a training step spends in the backward flash kernels (Pallas
calls named ``flash_bwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``); mean over
chips and traced steps."""
from perfbench import loader


def read(run):
    pt = loader.load_module("layer_metrics", "_program_trace")
    return pt.kernel_ms(pt.doc_of(run), pt.FLASH_BWD,
                        run["facts"].get("traced_steps"))
