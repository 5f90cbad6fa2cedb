"""Job kind ``serve_open``: an open loop. Requests are due at the
instants the schedule fixes from the traffic file's ``arrivals`` (a rate,
never searched for) and are sent whether or not earlier ones have
finished. A request is *attempted* if it was due before the window's last
``drain_s`` seconds (the longest request's service time, measured once);
arrivals go on to the window's end so that the load stays what it was;
an attempted request that has not finished when the window closes is
failed. Latencies count from the instant a request was DUE.
"""
from __future__ import annotations

from typing import Dict, List

from . import _serve


class Arrivals:
    def __init__(self, traffic: Dict, items: List[Dict]):
        self.items = sorted(items, key=lambda it: it["t"])
        self.next = 0
        self.drain_s = float(traffic["drain_s"])

    def due(self, now: float):
        while self.next < len(self.items) and self.items[self.next]["t"] <= now:
            item = self.items[self.next]
            self.next += 1
            yield item, item["t"]

    def next_due(self):
        return (self.items[self.next]["t"] if self.next < len(self.items)
                else None)

    def finished(self, item, now: float) -> None:
        pass

    def attempted(self, requests, seconds: float):
        return [r for r in requests if r.t_due < seconds - self.drain_s]


def run(ctx: Dict) -> Dict:
    return _serve.run(ctx, Arrivals)
