"""Infeed: host milliseconds a batch inside the host-to-device put alone
(``infeed_put_us`` over ``infeed_batches``; ``infeed_host_ms_per_batch`` adds
the assembly, which includes waiting for the feed)."""
import _per     # beside this file; run.py puts the directory on the path


def read(report):
    return _per.per(report, "infeed", ("infeed_put_us",),
                    "infeed_batches", scale=1e-3)
