"""What the traced window's batches waited, read from what the program
itself stamped on their ``predict.fetch`` spans (``tmr_tpu/inference.py``):
``ready_ts``, when the batch's answer became ready; ``service_s``, from when
the device was free for the batch (the later of its dispatch's return and
the ``ready_ts`` of the batch fetched before it) to ``ready_ts``;
``least_s``, the smallest the program holds that program and batch size to
in this process so far (the smallest middle value of five consecutive
``service_s``, the warm-up's batches among them); ``late``, where the host
came late and ``service_s`` is an upper bound only. The window's spans are
``program_span_ms.window_spans``'s, as for its siblings. A batch is held to
the smallest ``least_s`` among the window's batches of its program,
capacity and rows: the program's last word in the window, since
``least_s`` only falls. ``read`` chooses the number:

- ``stall_ms``: mean over the window's batches that are not ``late`` of
  ``service_s`` less that smallest: the program's own reading of what
  ``host.gap_ms`` reads from the device trace. Where the host noticed late
  that a batch was ready, that batch reads over and the next one as much
  under (``least_s`` is a middle value to stay clear of it), so the mean
  keeps what the device waited and loses what the host did;
- ``service_max_over_min``: the largest ``service_s`` over that smallest: 6
  says one batch stalled, 1.6 that all did; 1.3 beside a ``stall_ms`` near
  0 that the host noticed one batch late;
- ``d2h_ms``: mean over all the window's batches of the span's end less
  ``ready_ts``: the copy home.

Fewer than ``batches`` spans, a span without ``ready_ts`` (a program that
does not stamp it, an answer its dispatch did not note) or a window whose
every batch is ``late``: nothing returned, never 0."""

from benchmarks.reducers.program_span_ms import window_spans
from tmr_tpu.obs import tracing


def reduce(reduced: dict, spec: dict):
    mine = window_spans(tracing.spans(), spec["span"], reduced)
    if mine is None or any("ready_ts" not in r["attrs"] for r in mine):
        return None
    served = [r["attrs"] for r in mine if r["attrs"]["late"] is False]
    if not served:
        return None
    if spec["read"] == "d2h_ms":
        return 1e3 * sum(r["ts"] + r["dur"] - r["attrs"]["ready_ts"]
                         for r in mine) / len(mine)
    bucket = lambda a: (a.get("program"), a.get("capacity"), a.get("rows"))
    least = {}
    for a in served:
        least[bucket(a)] = min(a["least_s"],
                               least.get(bucket(a), a["least_s"]))
    if spec["read"] == "stall_ms":
        return 1e3 * sum(a["service_s"] - least[bucket(a)]
                         for a in served) / len(served)
    return max(a["service_s"] / least[bucket(a)] for a in served)
