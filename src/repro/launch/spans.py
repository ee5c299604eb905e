"""Host spans of the serving engine, kept in memory.

`span(name, **ids)` marks a stretch of host work twice: as a profiler
`TraceAnnotation` (so an operator's profile shows it beside the device's
operations), and as a record `(name, ids, t0_ns, t1_ns)` on `time.time_ns()`
appended to `RECORD` when the stretch ends. Ids are Python ints the caller
already holds, never a device array (reading one would wait for the device).

`RECORD` keeps the newest 2**16 spans: a whole serving run at about 190
spans a second (three a 16-ms decode tick, plus admissions) is about 21k.
There is no switch: with the profiler off a span costs about a microsecond.
A child's record precedes its parent's (records are appended at exit).

The spans and what reads them (the benchmark's `bench/spans.py`):
`serve.admit` (rid, slot, prompt_len) around each admission attempt in
`ServeEngine._admit`: pages, the prefill launch and the first token's sync;
`serve.decode` (active) around each decode tick, holding
`serve.decode.launch` (the decode program's call) and `serve.decode.fetch`
(the next tokens' copy to the host), the brackets that put these records on
a device trace's clock.
"""
from __future__ import annotations

import collections
import time

from jax.profiler import TraceAnnotation

RECORD = collections.deque(maxlen=1 << 16)


class span:
    """Context manager: one host span, recorded when it ends."""
    __slots__ = ("name", "ids", "t0", "_annotation")

    def __init__(self, name: str, **ids: int):
        self.name = name
        self.ids = ids
        self._annotation = TraceAnnotation(name, **ids)

    def __enter__(self):
        self._annotation.__enter__()
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        RECORD.append((self.name, self.ids, self.t0, time.time_ns()))
        return self._annotation.__exit__(*exc)
