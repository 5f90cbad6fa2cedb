"""How late the generator ran: 95th percentile of (instant submitted -
instant due) on the benchmark's clock, over every request of the window."""
from chipbench import stats


def read(facts):
    lag = facts.get("lag_s")
    if not lag or not facts["on_chip"]:
        return None
    return 1e3 * stats.percentile(lag, 95)
