"""What the benchmark reads from the serve loop while it runs.

The serve loop builds its scheduler as ``repro.runtime.scheduler.Scheduler``
and calls ``running()`` once per tick (after admission, before any device
work) and ``deliver()`` once per delivered token.  ``installed()`` puts a
subclass in that name for the length of one ``run_paged`` call.  The
subclass changes no decision; it records:

* per tick, the wall-clock start and each running request's prompt length,
  prefill position, decoding flag and delivered count — from which the
  harness counts the work of every tick (decode rows attended, prefill
  chunks) without guessing;
* per tick, the pool's free blocks;
* per delivered token, the wall clock and the request's arrival clock —
  the same two readings the scheduler's own TTFT and ITL histograms
  record, kept unquantized.

``on_tick(k)`` is called at the start of tick k; the harness starts and
stops the device trace from it.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field


@dataclass
class Record:
    ticks: list = field(default_factory=list)       # (t, [(id, plen, pf, dec, n_out)])
    deliveries: list = field(default_factory=list)  # (t, id, t_arrival)
    final: dict = field(default_factory=dict)       # id -> (plen, pf, n_out)
    pool_free: list = field(default_factory=list)   # free blocks per tick
    on_tick: object = None


def _state(r) -> tuple:
    return (r.id, r.plen, r.pf_pos, bool(r.decoding), len(r.out))


@contextlib.contextmanager
def installed(rec: Record):
    from repro.runtime import scheduler as mod
    base = mod.Scheduler

    class Tapped(base):
        def running(self):
            out = super().running()
            k = len(rec.ticks)
            if rec.on_tick is not None:
                rec.on_tick(k)
            rec.ticks.append((time.perf_counter(), [_state(r) for r in out]))
            rec.pool_free.append(self.pool.num_free)
            return out

        def deliver(self, r, token, now):
            rec.deliveries.append((now, r.id, r.t_arrival))
            super().deliver(r, token, now)

        def finish(self, r):
            super().finish(r)
            rec.final[r.id] = (r.plen, r.pf_pos, len(r.out))

    mod.Scheduler = Tapped
    try:
        yield rec
    finally:
        mod.Scheduler = base


@dataclass(frozen=True)
class TickWork:
    t: float
    decode_rows: tuple      # attended latent rows of each decoding slot
    chunks: tuple           # (start, c) of each prefill chunk run


def tick_work(rec: Record) -> list:
    """Work of every tick: the decode step over the slots decoding at the
    tick's start (a slot feeding its n-th token at position plen + n
    attends plen + n + 1 rows), and the prefill chunks, read as the
    advance of each request's prefill position by the next tick (or by
    its state at finish, for the last tick)."""
    out = []
    for i, (t, states) in enumerate(rec.ticks):
        nxt = {}
        if i + 1 < len(rec.ticks):
            nxt = {s[0]: s[2] for s in rec.ticks[i + 1][1]}
        rows, chunks = [], []
        for rid, plen, pf, dec, n_out in states:
            if dec:
                rows.append(plen + n_out + 1)
            else:
                after = nxt.get(rid)
                if after is None and rid in rec.final:
                    after = rec.final[rid][1]
                if after is not None and after > pf:
                    chunks.append((pf, after - pf))
        out.append(TickWork(t, tuple(rows), tuple(chunks)))
    return out
