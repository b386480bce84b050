"""Mean host milliseconds per step that the batcher spends admitting
requests and planning chunks (obs span ``serve/refill``) and on its
completion bookkeeping (``serve/complete``), from the program's own
spans in the traced run."""


def read(run):
    if not run.spans:
        return None
    ns, steps = 0, 0
    for e in run.spans:
        if e["ph"] == "X" and e["cat"] == "serve" \
                and e["name"] in ("refill", "complete"):
            ns += e["dur_ns"]
            steps += e["name"] == "refill"
    return ns / steps / 1e6 if steps else None
