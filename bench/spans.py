"""Host spans the benchmark records around calls into the program.

`Spans.wrap(module, attr, name)` replaces a module attribute for the
length of a `with` block by a wrapper that times every call on the
host clock and, while a profiler trace runs, also marks the call in the
trace (`jax.profiler.TraceAnnotation`), so idle gaps on the device can
be put down to what the host was doing.  With `keep=True` it also keeps
each call's arguments and result.
"""
from __future__ import annotations

import collections
import contextlib
import time


class Spans:
    def __init__(self, annotate: bool = False):
        self.seconds: dict[str, list[float]] = collections.defaultdict(list)
        self.kept: dict[str, list[tuple]] = collections.defaultdict(list)
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        """Time the enclosed block as one call of `name`."""
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(name)
        else:
            ctx = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ctx:
            yield
        self.seconds[name].append(time.perf_counter() - t0)

    @contextlib.contextmanager
    def wrap(self, module, attr: str, name: str, keep: bool = False):
        orig = getattr(module, attr)

        def wrapper(*args, **kwargs):
            with self.span(name):
                out = orig(*args, **kwargs)
            if keep:
                self.kept[name].append((args, out))
            return out

        setattr(module, attr, wrapper)
        try:
            yield
        finally:
            setattr(module, attr, orig)
