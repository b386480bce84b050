"""Mean host milliseconds between the device's steps: from the end of a
``serve/token_fetch`` (the host holds the step's tokens) to the end of
the next launch span, ``serve/prefill_chunk`` or ``serve/decode_launch``
(the device has its next program).  A gap counts only where the
``serve/complete`` after that fetch left work held (arg ``held`` > 0);
otherwise the batcher went on to wait for arrivals.  From the program's
own spans in the traced run."""

from chipbench.spans import serve

LAUNCHES = ("prefill_chunk", "decode_launch")


def gaps_ns(spans) -> list:
    fetched_ns, held, out = None, 0, []
    for e in serve(spans, ("token_fetch", "complete") + LAUNCHES):
        end = e["ts_ns"] + e["dur_ns"]
        if e["name"] == "token_fetch":
            fetched_ns, held = end, 0
        elif fetched_ns is None:
            continue
        elif e["name"] == "complete":
            held = e["args"]["held"]
        else:
            if held > 0:
                out.append(end - fetched_ns)
            fetched_ns = None
    return out


def read(run):
    gaps = gaps_ns(run.spans) if run.spans else []
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
