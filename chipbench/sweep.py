"""Finds an open-loop cell's knee once, by a sweep of fixed rates on the
chip; the benchmark itself never searches.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,3,4 [--schedule-seed <k>]

One process builds the cell once, then offers the cell's mix at each
rate for ``--seconds``.  Per rate it prints one JSON line: requests due
and finished, time to first token (median and 95th percentile, and the
median of the first and last third of the window's requests), gaps
between tokens, and the queue's depth at each quarter of the window.
The knee is the highest rate whose queue does not grow over the window.
``--schedule-seed`` offers the same mix in another order of lengths and
arrivals than the mix file's: a time-to-first-token tail over some fifty
requests follows where the long prompts land, so a gain in it is shown
under several orders.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from chipbench import run as R  # noqa: E402
from chipbench import traffic as T  # noqa: E402
from chipbench.spec import Cell  # noqa: E402
from chipbench.stats import percentile, token_gaps  # noqa: E402
from chipbench.window import Window  # noqa: E402


class QueueWindow(Window):
    """A window that also records the batcher's queue after each step."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.depth = []

    def step(self):
        super().step()
        self.depth.append((self.steps[-1].end_s, self.batcher.queued()))


def sweep_rate(setup, cell, rate: float, seed: int, seconds: float,
               schedule_seed=None) -> dict:
    b = setup.batcher
    mix = dict(cell.traffic, rate_rps=rate, drain_s=15)
    if schedule_seed is not None:
        mix["schedule_seed"] = schedule_seed
    offered = T.generate(mix, seed, seconds, cell.config["vocab"],
                         cell.config["cache_len"])
    w = QueueWindow(b, setup.request_cls)
    cell.driver().drive(w, offered, seconds, mix)
    # leave the batcher empty for the next rate: drop the drain's queue,
    # finish what is in the slots
    b.queue.clear()
    while any(r is not None for r in b.slot_req):
        b.step(-1)
    counted = sorted((s for s in w.served.values() if s.offered.counted),
                     key=lambda s: s.offered.due_s)
    ttft = [(s.token_s[0] if s.token_s else w.closed_s) - s.offered.due_s
            for s in counted]
    third = max(1, len(ttft) // 3)
    gaps = token_gaps(w)
    quarters = {}
    for q in (0.25, 0.5, 0.75, 1.0):
        seen = [d for t, d in w.depth if t <= q * seconds]
        quarters[str(q)] = seen[-1] if seen else 0
    return {"rate_rps": rate, "schedule_seed": mix["schedule_seed"],
            "due": len(counted),
            "finished_in_window": sum(1 for s in counted
                                      if s.done and s.token_s[-1] <= seconds),
            "unfinished_at_close": sum(1 for s in counted if not s.done),
            "closed_s": w.closed_s,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "ttft_p50_first_third_ms": 1e3 * percentile(ttft[:third], 50),
            "ttft_p50_last_third_ms": 1e3 * percentile(ttft[-third:], 50),
            "itl_p50_ms": 1e3 * percentile(gaps, 50),
            "itl_p95_ms": 1e3 * percentile(gaps, 95),
            "queue_at_quarters": quarters}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--schedule-seed", type=int, default=None)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    R.accelerator_devices(cell.chips)
    R.setup_compile_cache()
    setup = R.Setup(cell, args.seed, args.seconds)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        row = sweep_rate(setup, cell, rate, args.seed + k, args.seconds,
                         args.schedule_seed)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
