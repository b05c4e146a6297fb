"""Compile plane: seconds the chip-holding process spent tracing, lowering,
in the backend's compiler and reading the persistent cache before the window
(``compile_trace_us`` + ``compile_lower_us`` + ``compile_backend_us`` +
``compile_cache_retrieval_us`` of ``compilecache.stats``: no instant is in two
of them, a nested event's time is taken out of the one around it).  An "of
which": it lies inside the account's ``user``, ``trainer_init`` and
``first_dispatch``, not beside them."""
import _at_open    # beside this file; run.py puts the directory on the path


def read(report):
    return _at_open.total(report, "trainer", [
        "compile_trace_us", "compile_lower_us", "compile_backend_us",
        "compile_cache_retrieval_us"], scale=1e-6)
