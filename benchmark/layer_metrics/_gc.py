"""What the readers of Python's collector share: the traced span's
``python.gc`` spans (``paddle_tpu.profiler.trace_gc``: one a collection, on
whatever thread ran it) beside the device's idle time on the host's clock
(``benchmark/spans.py``'s bracket and cut).

A program without the hook has no ``python_gc_pause_seconds`` family in its
registry: its readers read nothing. A program with it reads 0 where no
collection fell inside the span.
"""
import functools

GC = "python.gc"
FAMILY = "python_gc_pause_seconds"


def instrumented():
    from paddle_tpu.observability import get_registry
    return get_registry().get(FAMILY) is not None


@functools.lru_cache(maxsize=4)
def load(path):
    """The file's events with the collector's spans among the host's."""
    from benchmark import spans
    return spans.read(path, also=(GC,))


def events(run):
    """The traced span's events, or None where the program has no hook or
    the run no trace."""
    from benchmark import spans
    path = spans.find_path(run)
    if not path or not instrumented():
        return None
    return load(path)


def pauses_ms(ev):
    """Each collection's length, ms."""
    return [(e - s) / 1e6 for name, s, e, _ in ev.host if name == GC]
