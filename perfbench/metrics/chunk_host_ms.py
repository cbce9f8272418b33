"""The host's own work per chunk (ms): the median, over the pool attempts
(``repro.pool.attempt`` spans) that started inside the window, of the
attempt's span less the wait for the device nested in it on the same
thread (``repro.chunk.wait``). It holds making the chunk's inputs,
dispatching the program, fetching the result and the pool's bookkeeping.
``None`` where no attempt span lies in the window."""
import program_trace


def read(view):
    marks = program_trace.of(view)
    if marks is None:
        return None
    return program_trace.host_ms_per_attempt(marks.spans, view.lo, view.hi)
