"""Window attention: of the causal ``[flash_block, flash_block]`` tiles of
the sliding layers' (queries, keys), the share that the forward kernel's grid
computes, over the window's steps and layers
(``Trainer.counters_snapshot()``: ``swa_tiles_computed`` /
``swa_tiles_causal``): the band's share for a grid that follows it, 100 for a
kernel that steps over the triangle and masks; ``None`` where the program
counts no such tiles."""
import _per    # beside this file; run.py puts the directory on the path


def read(report):
    return _per.per(report, "trainer", ["swa_tiles_computed"],
                    "swa_tiles_causal", scale=100.0)
