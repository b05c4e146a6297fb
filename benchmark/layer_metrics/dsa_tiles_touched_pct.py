"""Sparse attention: of the causal ``[flash_block, flash_block]`` tiles of
(queries, keys), the share that holds a kept key, over the window's steps and
layers (``Trainer.counters_snapshot()``: ``dsa_tiles_touched`` /
``dsa_tiles_causal``): 100 says a kernel that skipped empty tiles would skip
none on this data; ``None`` where the program counts no such tiles."""
import _per    # beside this file; run.py puts the directory on the path


def read(report):
    return _per.per(report, "trainer", ["dsa_tiles_touched"],
                    "dsa_tiles_causal", scale=100.0)
