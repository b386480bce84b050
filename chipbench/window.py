"""Drives the batcher on the wall clock and records when each generated
token reaches the host.

The window calls only ``ContinuousBatcher.submit`` and ``.step``, adds no
device synchronisation of its own, and reads the tokens a step appended
to each ``Request.tokens`` once that step has returned.  Every time is in
seconds since the window opened.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Dict, List

import jax


@dataclass
class Served:
    """One offered request as the window saw it."""

    offered: object                 # traffic.Offered
    req: object                     # the program's Request
    submitted_s: float
    token_s: List[float] = field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.token_s) >= self.offered.max_new

    def generated(self) -> List[int]:
        return list(self.req.tokens[len(self.req.prompt):])


@dataclass
class Step:
    start_s: float
    end_s: float
    #: cache position each token delivered by this step was decoded at
    positions: List[int]


class StallWatch:
    """Where the host is when one ``step()`` has run for longer than
    ``limit_s``: a daemon thread looks every ``poll_s`` and keeps the
    stepping thread's Python stack once per such step.  A step that
    stalls while the device is idle is the host's, and this says where
    in the host it waits."""

    def __init__(self, limit_s: float = 0.4, poll_s: float = 0.05):
        self.limit_s, self.poll_s = limit_s, poll_s
        #: (step index, seconds into the step, stack text)
        self.stacks: List[tuple] = []
        self._ident = threading.get_ident()
        #: (step index, start) of the step running now, swapped whole so
        #: that the watching thread never reads half of an update
        self._current = None
        self._taken = -1
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="chipbench-stall-watch")
        self._thread.start()

    def begin(self, index: int):
        self._current = (index, time.perf_counter())

    def end(self):
        self._current = None

    def _watch(self):
        while not self._stop.wait(self.poll_s):
            current = self._current
            if current is None or current[0] == self._taken:
                continue
            index, started = current
            held = time.perf_counter() - started
            frame = sys._current_frames().get(self._ident)
            if held > self.limit_s and frame is not None:
                stack = "".join(traceback.format_stack(frame)[-12:])
                self.stacks.append((index, held, stack))
                self._taken = index

    def close(self):
        self._stop.set()
        self._thread.join()


class Window:
    def __init__(self, batcher, request_cls, annotate: bool = False):
        self.batcher = batcher
        self.request_cls = request_cls
        self.annotate = annotate
        self.served: Dict[int, Served] = {}
        self.steps: List[Step] = []
        self.t0 = None
        self.closed_s = None
        self.stalls = None

    def _span(self, name: str):
        if self.annotate:
            return jax.profiler.TraceAnnotation(f"chipbench/{name}")
        return contextlib.nullcontext()

    def open(self):
        self.stalls = StallWatch()
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def close(self):
        self.closed_s = self.now()
        self.stalls.close()

    def submit(self, offered):
        with self._span("submit"):
            req = self.request_cls(rid=offered.rid, prompt=list(offered.prompt),
                                   max_new=offered.max_new,
                                   arrive_step=len(self.steps))
            self.batcher.submit(req)
            self.served[offered.rid] = Served(offered, req, self.now())

    def has_work(self) -> bool:
        b = self.batcher
        return b.queued() > 0 or any(r is not None for r in b.slot_req)

    def wait_until(self, t_s: float):
        with self._span("wait"):
            delay = t_s - self.now()
            if delay > 0:
                time.sleep(delay)

    def step(self):
        b = self.batcher
        before = [r for r in b.slot_req if r is not None]
        start = self.now()
        self.stalls.begin(len(self.steps))
        with self._span("step"):
            b.step(len(self.steps))
        end = self.now()
        self.stalls.end()
        with self._span("tokens"):
            watch = {id(r): r for r in before}
            watch.update((id(r), r) for r in b.slot_req if r is not None)
            positions = []
            for r in watch.values():
                rec = self.served[r.rid]
                produced = len(r.tokens) - len(r.prompt)
                for i in range(len(rec.token_s), produced):
                    rec.token_s.append(end)
                    positions.append(len(r.prompt) - 1 + i)
            self.steps.append(Step(start, end, positions))
