"""95th percentile over the requests due in the window of the time from
placement in a slot to the dispatch of the launch that put the last of
its prompt in the cache: the duration of its first ``serve/prompt``
span, whose prefix went in ``launches`` DLBC chunks.  From the
program's own spans in the traced run."""

from chipbench.spans import first_per_request_ms
from chipbench.stats import percentile


def read(run):
    times = first_per_request_ms(run, "prompt") if run.spans else []
    return percentile(times, 95) if times else None
