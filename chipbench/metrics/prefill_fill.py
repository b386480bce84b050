"""Prompt tokens per prefill launch over the launch buffer's capacity,
``n_slots x prefill_chunk``, in percent: the useful share of the prefill
program's work, from the program's ``serve/prefill_chunk`` spans."""


def read(run):
    if not run.spans:
        return None
    launches = [e for e in run.spans if e["ph"] == "X"
                and e["cat"] == "serve" and e["name"] == "prefill_chunk"]
    if not launches:
        return None
    tokens = sum(e["args"]["tokens"] for e in launches)
    return 100.0 * tokens / (len(launches) * run.n_slots * run.prefill_chunk)
