"""Device time the tick spends routing (``moe/route``), all expert layers,
in the two cells that hold a share of an expert-parallel layer. dots3: the
router's product, the rounds of argmax, the counting sort of the held
assignments; DeepSeek-V2: the softmax router under its group limit, the
counting sort of the held rows and the tick's routing statistics (four
expert layers); Ling-3.0-flash: scores over 512 experts, the group limit by
the sum of a group's two best biased scores, eight rounds of argmax, the
counting sort and the statistics (six expert layers)."""
from perfbench import loader


def read(run):
    return loader.load_module("layer_metrics", "_served").read_part(
        run, "route")
