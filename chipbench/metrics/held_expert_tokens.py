"""Mean tokens per held expert that a decode launch hits: over the
program's ``serve/decode_launch`` spans, the (token, choice) pairs on
held experts (arg ``routed_here``) over the held experts with at least
one (``experts_hit``), both summed over the expert layers."""

from chipbench.spans import serve


def read(run):
    if not run.spans:
        return None
    ratios = [e["args"]["routed_here"] / e["args"]["experts_hit"]
              for e in serve(run.spans, ("decode_launch",))
              if e["args"].get("experts_hit")]
    return sum(ratios) / len(ratios) if ratios else None
