"""Host-to-device uploads on a profiler trace's clock.

The program puts each chunk of host views on the chip with an explicit
``jax.device_put`` inside a ``transfer.h2d`` span. The span closes when
the call returns; the copy goes on after it, so the span alone is the
host's share of the upload. The TPU runtime records every
host-to-device transfer in the host planes: it is requested by a
``TpuClient::LinearizeIntoImpl`` event inside the ``device_put`` call,
and it has landed at the end of its
``tpu::System::TransferToDevice=>IssueEvent=>Done`` event. An upload
therefore lasts from its span's start until no requested transfer is
still on its way to the chip. Uploads queued back to back drain
together, so they are merged into one interval, never counted twice.

A CPU trace has no such events; there an upload is its span.
"""

from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

from traces import Interval, _union

SPAN = "transfer.h2d"
REQUEST = "TpuClient::LinearizeIntoImpl"
LANDED = "tpu::System::TransferToDevice=>IssueEvent=>Done"


def _drained(host) -> Tuple[List[float], List[int], List[float]]:
    """The transfers in flight as a step function: the times at which
    the count changes, the count from each of them on, and the times at
    which it falls to 0."""
    steps = sorted([(s, 1) for s, _, n in host if n == REQUEST]
                   + [(e, -1) for _, e, n in host if n == LANDED])
    times, counts, empty, n = [], [], [], 0
    for t, d in steps:
        n = max(0, n + d)
        times.append(t)
        counts.append(n)
        if n == 0 and d < 0:
            empty.append(t)
    return times, counts, empty


def uploads(trace, lo: float, hi: float) -> Optional[List[Interval]]:
    """The window's uploads, merged and clipped to [lo, hi]. None where
    the window holds no ``transfer.h2d`` span (the span was lost), or
    where a chip trace holds spans but no transfer request inside any of
    them (the runtime's events were renamed: the span alone would read
    only the host's share)."""
    spans = [(s, e) for s, e, n in trace.host
             if n == SPAN and e > lo and s < hi]
    if not spans:
        return None
    on_chip = any(dev.programs for dev in trace.devices.values())
    requests = sorted(s for s, _, n in trace.host if n == REQUEST)
    if on_chip and not any(bisect.bisect_left(requests, s)
                           < bisect.bisect_right(requests, e)
                           for s, e in spans):
        return None
    times, counts, empty = _drained(trace.host)
    out = []
    for s, e in spans:
        i = bisect.bisect_right(times, e) - 1
        if i >= 0 and counts[i] > 0:
            j = bisect.bisect_right(empty, e)
            e = empty[j] if j < len(empty) else hi
        out.append((max(s, lo), min(e, hi)))
    return _union(out)
