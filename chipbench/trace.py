"""From the profiler's trace to device time: which intervals the device
was busy, how long each launch of each step program ran on it, and what
the host was doing in the gaps.

On a TPU each ``/device:TPU:<n>`` plane has an ``XLA Modules`` line, one
event per launch named ``<jit name>(<program id>)`` with its ``run_id``,
and an ``XLA Ops`` line whose operations are given to the launch that
contains them.  On the CPU backend the executor threads' operations
carry ``hlo_module``, ``program_id`` and ``run_id`` themselves.

The two step programs are told apart by their entry shapes: before the
window ``Calibration`` launches each at its own shape, tokens
``(n_slots, 1)`` for decode and ``(n_slots, prefill_chunk)`` for
prefill, under a host annotation of its role, and the program that ran
inside one role's annotation and not the other's has that shape.  Launch
order and duration in the window are never used.

Host annotations are the benchmark's own (``chipbench/<what>``, around
the window, each submit, each ``step()``, the token bookkeeping and the
waits for arrivals).
"""

from __future__ import annotations

import bisect
import glob
import os
import time
import warnings
from collections import defaultdict
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

PREFIX = "chipbench/"
ROLES = ("decode", "prefill")
#: idle host time around each calibration launch, so that the device's
#: events fall inside the annotation whatever the offset between the
#: host's and the device's clocks
PAD_S = 0.005


class Calibration:
    """A launch of each step program at its own entry shape, on a cache
    of zeros made for that launch alone, so that the batcher's state is
    left alone and at most one extra cache and its output are live."""

    def __init__(self, batcher):
        n, c = batcher.n_slots, batcher.prefill_chunk
        zeros = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
        self.batcher = batcher
        self.fns = {"decode": batcher.decode_fn, "prefill": batcher.prefill_fn}
        self.batches = {
            "decode": {"tokens": zeros(n, 1), "cache_index": zeros(n)},
            "prefill": {"tokens": zeros(n, c), "cache_index": zeros(n),
                        "count": jnp.ones((n,), jnp.int32)}}
        for role in ROLES:        # compiled here, before any trace
            self._launch(role)

    def _launch(self, role):
        cache = jax.tree.map(jnp.zeros_like, self.batcher.cache)
        jax.block_until_ready(self.fns[role](self.batcher.params, cache,
                                             self.batches[role]))

    def launch(self):
        """Launch each program under ``chipbench/calibrate/<role>``."""
        for role in ROLES:
            with jax.profiler.TraceAnnotation(f"{PREFIX}calibrate/{role}"):
                time.sleep(PAD_S)
                self._launch(role)
                time.sleep(PAD_S)


@dataclass
class Event:
    start: int            # ns on the trace's clock
    end: int
    name: str
    program: str = ""     # "<jit name>(<program id>)"
    run: int = -1
    launch: bool = False  # one whole launch (a TPU module event)


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one trace under {directory}, "
                                f"found {found}")
    return found[0]


def union(intervals):
    """Sorted, disjoint ``[start, end)`` covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged, lo, hi) -> int:
    """Length of the disjoint ``merged`` intervals inside ``[lo, hi)``."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged)


def subtract(lo, hi, merged):
    """``[lo, hi)`` minus the disjoint ``merged`` intervals."""
    out, cur = [], lo
    for s, e in merged:
        if e <= cur or s >= hi:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def read_events(path: str):
    """``(device events by plane, host annotations)`` from an xplane
    file."""
    with warnings.catch_warnings():
        # the profiler's stats type warns on iteration under Python 3.12
        warnings.simplefilter("ignore", DeprecationWarning)
        data = jax.profiler.ProfileData.from_file(path)
        tpu = [p for p in data.planes if p.name.startswith("/device:TPU:")]
        device = {p.name: _tpu_events(p) for p in tpu}
        host = []
        cpu_ops = []
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    start = int(e.start_ns)
                    end = start + int(e.duration_ns)
                    if e.name.startswith(PREFIX):
                        host.append(Event(start, end, e.name))
                    elif not tpu:
                        st = dict(e.stats)
                        if "program_id" in st:
                            cpu_ops.append(Event(
                                start, end, e.name,
                                f"{st.get('hlo_module')}({st['program_id']})",
                                int(st.get("run_id", -1))))
        if not tpu and cpu_ops:
            device["/host:CPU"] = cpu_ops
    return device, host


def _tpu_events(plane) -> list:
    modules, ops = [], []
    for line in plane.lines:
        if line.name not in ("XLA Modules", "XLA Ops"):
            continue
        for e in line.events:
            start = int(e.start_ns)
            end = start + int(e.duration_ns)
            if line.name == "XLA Modules":
                run = dict(e.stats).get("run_id", -1)
                modules.append(Event(start, end, e.name, e.name, int(run),
                                     launch=True))
            else:
                # "%fusion.12 = bf16[...] fusion(...)" -> "fusion.12"
                ops.append(Event(start, end,
                                 e.name.split(" = ")[0].lstrip("%")))
    modules.sort(key=lambda m: m.start)
    # an operation that contains the next one (a while loop around its
    # body's operations) is not counted apart from what it contains
    ops.sort(key=lambda o: o.start)
    ops = [o for o, nxt in zip(ops, ops[1:] + [None])
           if nxt is None or nxt.start >= o.end]
    starts = [m.start for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < modules[i].end:
            op.program, op.run = modules[i].program, modules[i].run
    return modules + ops


@dataclass
class Summary:
    """Device time over the traced window, averaged over the chips."""

    window_s: float
    busy_s: float
    pending_s: float
    idle_share: float
    launches: dict                       # role -> [device seconds]
    top_ops: list = field(default_factory=list)
    gaps: list = field(default_factory=list)

    def mean_launch_s(self, role: str) -> float:
        return float(np.mean(self.launches[role]))

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops, "idle_gaps": self.gaps}


def _programs(events, calib_spans) -> dict:
    """role -> program: of the programs that ran inside a role's
    calibration annotation and not inside the other role's, the one with
    the most device time there."""
    seen = {}
    for role, span in calib_spans.items():
        time_in = defaultdict(int)
        for e in events:
            if e.program and span.start <= e.start and e.end <= span.end:
                time_in[e.program] += e.end - e.start
        seen[role] = time_in
    out = {}
    for role, time_in in seen.items():
        others = set().union(*(v for r, v in seen.items() if r != role))
        mine = {p: t for p, t in time_in.items() if p not in others}
        if mine:
            out[role] = max(mine, key=mine.get)
    return out


def reduce(path: str, n_chips: int = 1, top: int = 10) -> Summary:
    return reduce_events(*read_events(path), n_chips=n_chips, top=top)


def reduce_events(device: dict, host: list, n_chips: int = 1,
                  top: int = 10) -> Summary:
    windows = [h for h in host if h.name == f"{PREFIX}window"]
    if len(windows) != 1:
        raise ValueError(f"expected one {PREFIX}window annotation, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    calib = {h.name.rsplit("/", 1)[1]: h for h in host
             if h.name.startswith(f"{PREFIX}calibrate/")}
    waits = union((h.start, h.end) for h in host
                  if h.name == f"{PREFIX}wait")
    pending = subtract(lo, hi, waits)
    pending_ns = sum(e - s for s, e in pending)
    planes = sorted(device)[:n_chips]
    if not planes:
        raise ValueError("the trace holds no device events")
    busy, busy_pending, launches = [], [], defaultdict(list)
    ops, gaps = defaultdict(float), []
    for plane in planes:
        events = device[plane]
        roles = _programs(events, calib)
        merged = union((e.start, e.end) for e in events
                       if e.end > lo and e.start < hi)
        busy.append(overlap(merged, lo, hi))
        busy_pending.append(sum(overlap(merged, s, e) for s, e in pending))
        by_launch = defaultdict(list)
        name_of = {v: k for k, v in roles.items()}
        has_modules = any(e.launch for e in events)
        for e in events:
            if not lo <= e.start < hi or e.program not in name_of:
                continue
            if e.launch or not has_modules:
                by_launch[(e.program, e.run)].append((e.start, e.end))
            if not e.launch:
                ops[f"{name_of[e.program]}:{e.name}"] += (e.end - e.start) / 1e9
        for (program, _), spans in by_launch.items():
            length = sum(b - a for a, b in union(spans))
            launches[name_of[program]].append(length / 1e9)
        for s, e in subtract(lo, hi, merged):
            gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=float(np.mean(busy)) / 1e9,
        pending_s=pending_ns / 1e9,
        idle_share=(1.0 - float(np.mean(busy_pending)) / pending_ns
                    if pending_ns else 0.0),
        launches=dict(launches),
        top_ops=[[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])
                 [:top]],
        gaps=[[_doing(host, s, e), n / 1e9] for n, s, e in gaps[:top]])


def _doing(host, s, e) -> str:
    """What the host was doing in ``[s, e)``: the benchmark annotation
    (other than the window itself) that covers most of it."""
    best, name = 0, "outside any annotation"
    for h in host:
        if h.name == f"{PREFIX}window":
            continue
        covered = max(0, min(h.end, e) - max(h.start, s))
        if covered > best:
            best, name = covered, h.name[len(PREFIX):]
    return name
