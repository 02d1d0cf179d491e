"""Spans, counters and compile counts of the serving program.

Spans.  ``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: while
a profiler session runs it lands in the profiler's host plane, on the same
clock as the device plane, with ``args`` as the event's metadata; with no
session it costs about a microsecond, so the spans are always in the code
and there is no switch.  One tick of the overlapped engine
(``DecodeEngine._step_overlapped``) nests as::

    serving.tick {n, gen, parked}       one engine.step
      serving.finalize                  Scheduler.finalize of last tick
        serving.fetch                   its tokens to the host (blocks)
      serving.admit {admitted, queued, gated}
      serving.fill                      this tick's host buffers
      serving.decode                    the decode dispatch
        serving.tables                  a block-table upload, when stale
      serving.prefill {rid, slot, start, n, final}
        serving.tables
        serving.first_token             the final chunk's blocking fetch
      serving.advance                   Scheduler.tick_advance
      serving.admit ...                 the admission under the running step
      serving.prestage

The serial and speculative ticks use the same names (``serving.verify``
and ``serving.draft`` for the speculative dispatches).  The front end
adds ``server.submit`` and ``server.publish`` (``launch/server.py``).
Spans of one request carry its ``rid``.

Counters.  :class:`Counters` is one plain record of ints that the engine
owns and the scheduler and the paged cache manager increment at the same
boundaries.  ``snapshot()`` and ``since(snapshot)`` give the counts of an
interval.

Compiles.  :func:`compiles` is the process-wide count of XLA compiles by
function name (``jit(_fused)``, ``jit(_prefill)``, ...) and
:func:`compile_seconds` their summed time, from the event JAX's
monitoring reports for every backend compile (a load from the persistent
compile cache included).
"""

from __future__ import annotations

import collections
import dataclasses

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str, **args):
    """A host span named ``name`` with integer metadata ``args``."""
    return jax.profiler.TraceAnnotation(name, **args)


@dataclasses.dataclass
class Counters:
    """What the serving program did, counted where it was done."""
    ticks: int = 0                # engine ticks that ran work
    decode_dispatches: int = 0    # batched decode (or verify) dispatches
    decode_slots: int = 0         # generating slots, summed over those
    prefill_chunks: int = 0       # chunked-prefill dispatches
    prefill_tokens: int = 0       # prompt tokens those chunks consumed
    first_token_syncs: int = 0    # final chunks' blocking token fetches
    admissions: int = 0           # requests given a slot
    admit_gated_pool: int = 0     # waves whose head the pool refused
    admit_gated_slots: int = 0    # waves with a queue and no free slot
    table_uploads: int = 0        # block tables copied to the device
    spec_drafted: int = 0         # draft tokens proposed
    spec_accepted: int = 0        # draft tokens the target accepted
    spec_emitted: int = 0         # tokens recorded from verify windows
    spec_windows: int = 0         # verify windows (slot x tick)

    def snapshot(self) -> "Counters":
        return dataclasses.replace(self)

    def since(self, snap: "Counters") -> "Counters":
        """The counts accumulated after ``snap`` was taken."""
        return Counters(**{
            f.name: getattr(self, f.name) - getattr(snap, f.name)
            for f in dataclasses.fields(self)})


_compiles: collections.Counter = collections.Counter()
_compile_s = [0.0]


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    if event == COMPILE_EVENT:
        _compiles[kwargs.get("fun_name", "?")] += 1
        _compile_s[0] += duration


def compiles() -> dict:
    """XLA compiles in this process so far, by function name."""
    return dict(_compiles)


def compile_seconds() -> float:
    """Seconds XLA spent on those compiles."""
    return _compile_s[0]


jax.monitoring.register_event_duration_secs_listener(_on_duration_event)
