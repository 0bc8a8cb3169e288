"""How unevenly the router loads the experts: the rows of the fullest
expert over the mean expert's, averaged over the expert-layer calls (layers
x micro-batches) of the window's last step. The program counts them: they
are outputs of the training step itself
(``HybridPipelineTrainer.aux_stats``, ``moe/load_max`` and
``moe/assigned``), which the family reads after the step. 1 is balanced;
drop-less routing computes every row whatever this reads."""


def read(run):
    return run["facts"].get("moe_expert_load_max_over_mean")
