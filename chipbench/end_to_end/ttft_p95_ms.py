"""95th percentile of time to first token over every request due in the
window, from its due time to the return of the step that delivered its
first token.  A request that never got one counts with the time it had
waited when the run stopped serving."""

from chipbench.stats import percentile


def read(run):
    w = run.window
    waits = []
    for s in w.served.values():
        if not s.offered.counted:
            continue
        first = s.token_s[0] if s.token_s else w.closed_s
        waits.append(first - s.offered.due_s)
    return 1e3 * percentile(waits, 95)
