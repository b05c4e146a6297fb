"""Expert layer: the heaviest held expert's token-slots over the mean held
expert's, summed over the expert layers and the window's steps
(``Trainer.counters_snapshot()``: ``moe_expert_load_max_sum`` /
``moe_expert_load_mean_sum``): 1.0 is even routing; the grouped products'
work follows the sum, their longest group the maximum."""
import _per    # beside this file; run.py puts the directory on the path


def read(report):
    return _per.per(report, "trainer", ["moe_expert_load_max_sum"],
                    "moe_expert_load_mean_sum")
