"""``setup_s`` less the five rows (before the program, import, weights,
build, first calls) and less the traffic's ``warm_in_s`` (serving): the
harness's plan, the warm steps' or the warm-up's execution, and any hold of
the machine outside a phase. The identity: the five rows + ``warm_in_s`` +
this = ``setup_s``."""
from perfbench import loader


def read(run):
    rows = loader.load_module("layer_metrics", "_setup").rows(run)
    if rows is None:
        return None
    ctx = run["ctx"]
    return ctx.setup_s - sum(rows.values()) \
        - ctx.traffic.get("warm_in_s", 0.0)
