"""Host spans of the serving loop, on the device trace's clock.

``span(name, **meta)`` marks host work of the serving loop as a
``jax.profiler.TraceAnnotation``.  A profiler session records it in the
same ``.xplane.pb`` as the device's ops, on the same clock, so an idle
stretch of the device can be named by the host work that held it; this
module keeps nothing itself.  ``enable`` switches spans on or off for
the whole process.  Off, the default, ``span`` returns one shared null
context and makes no profiler call.

Metadata values are ints, or strings without ``,``, ``#`` or ``=``.
The profiler cuts a value at a comma, so lists are joined with ``_``,
and it reads any value it can parse as a number as one (``0x3`` is
3.0), so pairs of numbers are joined with ``:``.  Values known only
inside a span go in through its ``set_metadata``.  The null context
enters as ``None``, so ``with span(...) as sp:`` followed by ``if sp is
not None: sp.set_metadata(...)`` builds them only when tracing is on.

Spans of the loop, outermost first: ``serve.call`` (one ``generate``),
``sched.turn`` (one scheduler turn), and inside a turn ``sched.policy``,
``sched.admit`` (one prefill group), ``sched.page`` (demand paging),
``sched.round`` (one round; the collaborative engine nests
``engine.dispatch`` and ``engine.sync`` in it) and ``sched.commit`` (the
round's bookkeeping, with ``<uid>:<tokens>`` of every request it
served); ``sched.finalize`` closes the call with the one device-to-host
transfer of its tokens.  ``read`` gives them back from a recorded
profile.
"""
from __future__ import annotations

import contextlib
import glob
import os
from typing import Callable, Dict, Iterator, List, Tuple

import jax

__all__ = ["enable", "span", "turns", "read"]

PREFIXES = ("serve.", "sched.", "engine.")

_NULL = contextlib.nullcontext()
_on = False


def enable(on: bool) -> None:
    """Record the serving loop's spans from now on (``True``) or not."""
    global _on
    _on = bool(on)


def span(name: str, **meta):
    """A ``TraceAnnotation`` named ``name`` when tracing is on, else the
    shared null context."""
    if not _on:
        return _NULL
    return jax.profiler.TraceAnnotation(name, **meta)


def turns(live: Callable[[], bool]) -> Iterator[int]:
    """Turn numbers of the scheduler's loop while ``live()`` holds, each
    turn inside a ``sched.turn`` span."""
    turn = 0
    while live():
        with span("sched.turn", turn=turn):
            yield turn
        turn += 1


def read(trace_dir: str) -> List[Tuple[str, int, int, Dict[str, object]]]:
    """The serving loop's spans in the newest profile under
    ``trace_dir``: ``(name, start_ns, end_ns, metadata)``, by start
    (an outer span before the spans it holds)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    spans = [(ev.name, int(ev.start_ns), int(ev.end_ns), dict(ev.stats))
             for plane in pd.planes for line in plane.lines
             for ev in line.events if ev.name.startswith(PREFIXES)]
    return sorted(spans, key=lambda s: (s[1], -s[2]))
