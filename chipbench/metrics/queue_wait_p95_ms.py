"""95th percentile over the requests due in the window of the time each
waited in the batcher's queue before a slot took it: the duration of its
first ``serve/queued`` span (submit to placement), from the program's
own spans in the traced run."""

from chipbench.spans import first_per_request_ms
from chipbench.stats import percentile


def read(run):
    waits = first_per_request_ms(run, "queued") if run.spans else []
    return percentile(waits, 95) if waits else None
