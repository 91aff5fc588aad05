"""Mean host milliseconds a batch that the program itself spent in the span
named ``span``, read from the program's own always-on coarse spans
(``tmr_tpu/obs/tracing.py``; the ring is this process's, and still there
when the trace is reduced): the last ``batches`` such spans by start time
are the traced window's, since nothing calls the program between the
window's end and the reduction. *Self* time: a span's duration less what
its children cover, a child being a span that names it as ``parent`` or
lies inside it on the same thread. Fewer than ``batches`` such spans (a
program without them records none), or spans reaching over more than the
window: nothing returned, never 0."""

from benchmarks import trace
from tmr_tpu.obs import tracing


def window_spans(spans: list, name: str, reduced: dict):
    """The traced window's spans named ``name``, oldest first, or None."""
    n = reduced["batches"]
    mine = [r for r in spans if r["name"] == name]
    if not n or len(mine) < n:
        return None
    last = mine[-n:]
    reach = last[-1]["ts"] + last[-1]["dur"] - last[0]["ts"]
    return last if reach <= reduced["window_s"] else None


def self_seconds(rec: dict, spans: list) -> float:
    t0, t1 = rec["ts"], rec["ts"] + rec["dur"]
    covered = []
    for c in spans:
        c0, c1 = c["ts"], c["ts"] + c["dur"]
        # of two spans with the same stamps the later one is the child
        inside = (c["tid"] == rec["tid"] and t0 <= c0 and c1 <= t1
                  and (c["dur"] < rec["dur"] or c["span"] > rec["span"]))
        if c is not rec and (c["parent"] == rec["span"] or inside):
            covered.append([max(c0, t0), min(c1, t1)])
    return rec["dur"] - sum(e - s for s, e in trace._union(covered) if e > s)


def reduce(reduced: dict, spec: dict):
    spans = tracing.spans()
    mine = window_spans(spans, spec["span"], reduced)
    if mine is None:
        return None
    return 1e3 * sum(self_seconds(r, spans) for r in mine) / len(mine)
