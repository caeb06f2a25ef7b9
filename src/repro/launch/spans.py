"""Span recorder of the serving loop: where one run's host time goes.

One :class:`SpanRecorder` per ``MultiTenantServer`` keeps, for one
``run()`` (``reset`` starts the next), three in-memory records on one
monotonic clock (:func:`now`, which every stamp of the server reads):

* **spans** at the serving loop's layer boundaries (``serve.admit``,
  ``serve.plan``, ``serve.dispatch``, ``serve.first_token``,
  ``serve.finish``), each with the epoch it belongs to and the tenant
  id where it has one.  ``serve.plan`` is recorded as segments around
  ``serve.admit``, so no two top-level spans overlap;
* **per-task stamps**: ``due``, ``admit_start``, ``admit_end``,
  ``first_dispatch`` and ``first_token``, so that a task's time to
  first token splits into its wait, its admission and its prefill;
* **per-epoch counters** taken where the epoch is dispatched.

Every span is also entered as a ``jax.profiler.TraceAnnotation`` under
its bare name with ``epoch`` (and ``tid``) as stats, so a profiler trace
shows it on the host plane, on the device trace's clock.  With no trace
running an annotation costs about a microsecond.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation

now = time.perf_counter

ADMIT = "serve.admit"
PLAN = "serve.plan"
DISPATCH = "serve.dispatch"
FIRST_TOKEN = "serve.first_token"
FINISH = "serve.finish"
NAMES = (ADMIT, PLAN, DISPATCH, FIRST_TOKEN, FINISH)

STAMPS = ("due", "admit_start", "admit_end", "first_dispatch",
          "first_token")

# one recorded span: (name, start, end, epoch, tid, depth) — depth 0 is
# a top-level span of the serving thread
Record = Tuple[str, float, float, int, Optional[str], int]


class Span:
    """One span, entered as a context manager: ``start`` and ``end`` are
    the recorder clock's reads, taken inside the profiler annotation."""
    __slots__ = ("rec", "name", "tid", "epoch", "start", "end", "depth",
                 "_ann")

    def __init__(self, rec: "SpanRecorder", name: str,
                 tid: Optional[str]):
        self.rec, self.name, self.tid = rec, name, tid

    def __enter__(self) -> "Span":
        rec = self.rec
        if self.name == ADMIT:
            rec._pause_plan()
        self.epoch = rec.epoch
        self.depth = rec._depth
        rec._depth += 1
        self._ann = (TraceAnnotation(self.name, epoch=self.epoch)
                     if self.tid is None else
                     TraceAnnotation(self.name, epoch=self.epoch,
                                     tid=self.tid))
        self._ann.__enter__()
        self.start = now()
        return self

    def __exit__(self, *exc) -> None:
        self.end = now()
        self._ann.__exit__(*exc)
        rec = self.rec
        rec._depth -= 1
        rec.records.append((self.name, self.start, self.end, self.epoch,
                            self.tid, self.depth))
        if self.name == ADMIT:
            rec._resume_plan()


class SpanRecorder:
    """Spans, per-task stamps and per-epoch counters of one run."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        """Start a run: drop the last run's records (memory is bounded
        by one run)."""
        self.t0 = now()
        # the epoch being planned or dispatched: the count of epochs
        # dispatched so far
        self.epoch = 0
        self.records: List[Record] = []
        self.tasks: Dict[str, Dict[str, Optional[float]]] = {}
        self.epoch_log: List[Dict[str, int]] = []
        self._depth = 0
        self._plan: Optional[Span] = None     # open plan segment
        self._planning = False

    def span(self, name: str, tid: Optional[str] = None) -> Span:
        return Span(self, name, tid)

    # ------------------------------------------------------ planning --
    @contextlib.contextmanager
    def plan(self) -> Iterator[None]:
        """The planning call of one epoch, recorded as ``serve.plan``
        segments that exclude every ``serve.admit`` inside it."""
        self._planning = True
        self._resume_plan()
        try:
            yield
        finally:
            self._planning = False
            self._pause_plan()

    def _pause_plan(self) -> None:
        if self._plan is not None:
            self._plan.__exit__(None, None, None)
            self._plan = None

    def _resume_plan(self) -> None:
        if self._planning and self._plan is None:
            self._plan = self.span(PLAN).__enter__()

    # --------------------------------------------------------- tasks --
    def task(self, tid: str, due: float) -> Dict[str, Optional[float]]:
        rec = self.tasks.get(tid)
        if rec is None:
            rec = self.tasks[tid] = dict.fromkeys(STAMPS)
        rec["due"] = due
        return rec

    def stamp(self, tid: str, key: str, when: float) -> None:
        """Set one stamp of a task of this run (tasks of earlier runs
        are not recorded)."""
        rec = self.tasks.get(tid)
        if rec is not None:
            rec[key] = when

    # -------------------------------------------------------- epochs --
    def log_epoch(self, **counters: int) -> None:
        """The dispatched epoch's counters; the next epoch begins."""
        counters["epoch"] = self.epoch
        self.epoch_log.append(counters)
        self.epoch += 1

    # -------------------------------------------------------- export --
    def totals(self) -> Dict[str, Dict[str, float]]:
        """Seconds and count per span name."""
        out = {n: {"seconds": 0.0, "count": 0} for n in NAMES}
        for name, s, e, *_ in self.records:
            out[name]["seconds"] += e - s
            out[name]["count"] += 1
        return out

    def durations(self, name: str) -> List[float]:
        return [e - s for n, s, e, *_ in self.records if n == name]

    def per_epoch(self, name: str) -> List[float]:
        """Seconds of ``name`` per epoch index, from 0 to the last epoch
        that has one."""
        out: List[float] = []
        for n, s, e, epoch, *_ in self.records:
            if n == name:
                out.extend([0.0] * (epoch + 1 - len(out)))
                out[epoch] += e - s
        return out

    def task_stamps(self) -> Dict[str, Dict[str, Optional[float]]]:
        """Every task's stamps in seconds from the run's start."""
        return {tid: {k: (None if v is None else v - self.t0)
                      for k, v in rec.items()}
                for tid, rec in self.tasks.items()}

