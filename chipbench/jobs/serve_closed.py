"""Job kind ``serve_closed``: a closed loop of ``arrivals.clients``
callers, each sending its next request the moment the last returns (an
offline job over a pool of ``arrivals.pool`` documents, taken in the
seed's order). Saturated by construction: completed tokens per second is
what such a cell judges. With more callers than the engine has lanes most
of a request's life is spent waiting for a lane, so what is still out when
the window closes is as a rule unfinished work, not a failure. Attempted
are the requests that RETURNED inside the window and those that have been
out for longer than the longest of the returned took (all that were sent,
where none returned); failed are the attempted that did not return ``ok``
with every token asked for.
"""
from __future__ import annotations

from typing import Dict, List

from . import _serve


class Clients:
    def __init__(self, traffic: Dict, items: List[Dict]):
        self.items, self.next = items, 0
        self.waiting = int(traffic["arrivals"]["clients"])  # callers idle

    def due(self, now: float):
        while self.waiting and self.next < len(self.items):
            item = self.items[self.next]
            self.next += 1
            self.waiting -= 1
            yield item, now

    def next_due(self):
        return None

    def finished(self, item, now: float) -> None:
        self.waiting += 1

    def attempted(self, requests, seconds: float):
        back = [r for r in requests if r.t_done is not None]
        longest = max((r.t_done - r.t_submit for r in back), default=0.0)
        return back + [r for r in requests if r.t_done is None
                       and seconds - r.t_submit > longest]


def run(ctx: Dict) -> Dict:
    return _serve.run(ctx, Clients)
