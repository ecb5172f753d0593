"""Host counters summed per request, kept with telemetry off.

A `RequestAccount` sums named integer counters under the ordinal of the
request open when they are added (`compile_account.open_request`; 0
outside any request), for the life of the process and the last
`_KEEP_REQUESTS` ordinals.  Every count is a host integer the program
already holds: adding one reads nothing back from the device.

`shape_account` keeps what the program decides from shapes alone:

  * `coarsening_levels`: coarse levels a request's coarsener built (the
    hierarchy's depth under `deep` and `kway`);
  * `conn_scatter_calls`: refiner calls whose conn table took the
    `segment_sum` scatter rather than the stream
    (`ops/segments.count_conn_engine`);
  * `level0_edge_slots` and `level0_padded_edge_slots`: the real and
    the padded edge slots of the request's own graph as uploaded
    (`graphs/csr.upload_input_graph`).

The benchmark's `coarsening_levels`, `conn_scatter_calls` and
`level0_pad_share` read it;
`refinement/fm.fm_account` is the same kind of account for host FM.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

from . import compile_account

_KEEP_REQUESTS = 64  # the last so many requests' sums


class RequestAccount:
    """`counters` summed per request ordinal (0 outside any request)."""

    def __init__(self, counters: tuple) -> None:
        self.counters = tuple(counters)
        self._lock = threading.Lock()
        self._by_request: "OrderedDict[int, dict]" = OrderedDict()

    def add(self, counts: dict) -> None:
        """Add `counts` (a subset of `counters`) to the open request."""
        ordinal = compile_account.open_request()
        with self._lock:
            entry = self._by_request.get(ordinal)
            if entry is None:
                entry = self._by_request[ordinal] = dict.fromkeys(
                    self.counters, 0)
                while len(self._by_request) > _KEEP_REQUESTS:
                    self._by_request.popitem(last=False)
            for name, value in counts.items():
                entry[name] += value

    def summary(self) -> dict:
        """`{"requests": requests begun, "by_request": {ordinal: {...}}}`;
        a request that added nothing has no entry."""
        with self._lock:
            return {"requests": compile_account.requests_begun(),
                    "by_request": {k: dict(v)
                                   for k, v in self._by_request.items()}}


shape_account = RequestAccount((
    "coarsening_levels", "conn_scatter_calls",
    "level0_edge_slots", "level0_padded_edge_slots",
))
