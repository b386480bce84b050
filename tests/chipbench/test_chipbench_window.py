"""Open-loop timing on the wall clock, with a stand-in batcher whose
steps take a known time: latency counts from the due time, a stalled
step shows in the tail and leaves the host's stack, and a request never
served counts with its wait."""

import time
from dataclasses import dataclass, field
from types import SimpleNamespace

import pytest

from chipbench.drivers import backlog, open_loop
from chipbench.end_to_end import (itl_mean_ms, itl_p95_ms, output_tok_s,
                                  ttft_p95_ms)
from chipbench.traffic import Offered
from chipbench.window import Window


@dataclass
class Req:
    rid: int
    prompt: list
    max_new: int
    arrive_step: int = 0
    tokens: list = field(default_factory=list)


class FakeBatcher:
    """Every step takes ``step_s`` (``stall_s`` for step ``stall_at``),
    admits queued requests into free slots and gives every admitted one
    a token; rids in ``never`` are never admitted."""

    def __init__(self, n_slots=2, step_s=0.004, stall_at=None, stall_s=0.0,
                 never=()):
        self.slot_req = [None] * n_slots
        self.queue = []
        self.step_s, self.stall_at, self.stall_s = step_s, stall_at, stall_s
        self.never = set(never)
        self.stats = SimpleNamespace(failed=0, expired=0, truncated=0)

    def submit(self, req):
        self.queue.append(req)

    def queued(self):
        return len(self.queue)

    def step(self, now):
        for i, r in enumerate(self.slot_req):
            if r is None:
                ok = [q for q in self.queue if q.rid not in self.never]
                if ok:
                    self.queue.remove(ok[0])
                    ok[0].tokens = list(ok[0].prompt)
                    self.slot_req[i] = ok[0]
        time.sleep(self.stall_s if now == self.stall_at else self.step_s)
        for i, r in enumerate(self.slot_req):
            if r is not None:
                r.tokens.append(7)
                if len(r.tokens) - len(r.prompt) >= r.max_new:
                    self.slot_req[i] = None


def _run(batcher, offered, seconds, driver=open_loop, drain_s=2.0):
    w = Window(batcher, Req)
    driver.drive(w, offered, seconds, {"drain_s": drain_s})
    return SimpleNamespace(window=w, seconds=seconds)


def _offered(dues, max_new=3, counted=True):
    return [Offered(rid=i, due_s=d, prompt=[1, 2], max_new=max_new,
                    counted=counted) for i, d in enumerate(dues)]


def test_latency_counts_from_due_time_not_submission():
    # a stalled step holds the generator: requests due during the stall
    # are submitted late, and their first-token time counts from due
    dues = [0.0] + [0.05 + 0.01 * i for i in range(19)]
    run = _run(FakeBatcher(n_slots=8, stall_at=0, stall_s=0.4), _offered(dues),
               0.3)
    w = run.window
    late = [s.submitted_s - s.offered.due_s for s in w.served.values()]
    assert max(late) > 0.2
    ttft = [s.token_s[0] - s.offered.due_s for s in w.served.values()]
    assert min(ttft) > 0
    # half the requests were due in the stall: the tail is stall-sized
    assert ttft_p95_ms.read(run) > 250
    calm = _run(FakeBatcher(n_slots=8), _offered(dues), 0.3)
    assert ttft_p95_ms.read(calm) < 100


def test_unserved_request_counts_with_its_wait():
    # rid 0 is never admitted: the run serves until the drain runs out
    # and the request counts with the time it waited, beyond the others
    run = _run(FakeBatcher(never={0}), _offered([0.0, 0.0, 0.01, 0.02]), 0.05,
               drain_s=0.3)
    w = run.window
    assert not w.served[0].token_s
    assert w.closed_s >= 0.35
    waits = sorted(((s.token_s[0] if s.token_s else w.closed_s)
                    - s.offered.due_s) for s in w.served.values())
    assert waits[-1] == pytest.approx(w.closed_s, abs=1e-9)
    assert ttft_p95_ms.read(run) > 1e3 * 0.8 * w.closed_s


def test_token_gaps_and_percentiles_over_all_requests():
    run = _run(FakeBatcher(n_slots=4, step_s=0.01), _offered([0.0] * 4, 5),
               0.01)
    for s in run.window.served.values():
        assert len(s.token_s) == 5
    # 4 requests x 4 gaps, each one step of about 10 ms
    assert 9 <= itl_mean_ms.read(run) <= 30
    assert itl_p95_ms.read(run) >= itl_mean_ms.read(run)


def test_backlog_counts_tokens_inside_the_window_only():
    run = _run(FakeBatcher(n_slots=2, step_s=0.01), _offered([0.0] * 50, 4),
               0.2, driver=backlog)
    inside = sum(1 for s in run.window.served.values() for t in s.token_s
                 if t <= 0.2)
    assert output_tok_s.read(run) == pytest.approx(inside / 0.2)
    assert 10 <= inside <= 2 * 20
    assert run.window.closed_s >= 0.2


def test_stall_watch_keeps_the_stack_of_a_long_step():
    run = _run(FakeBatcher(n_slots=2, stall_at=3, stall_s=0.6),
               _offered([0.0, 0.0], 8), 0.05)
    stalls = run.window.stalls.stacks
    assert [index for index, _, _ in stalls] == [3]
    _, held, stack = stalls[0]
    assert 0.4 < held < 0.6
    assert "in step" in stack and "time.sleep" in stack
    assert not run.window.stalls._thread.is_alive()
