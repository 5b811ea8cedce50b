"""95th percentile, over every query of the window, of dispatch to logits
on the host (in pool cells the online phase, staging excluded)."""
import statistics


def read(run):
    if len(run.online_s) < 20:
        return None
    return statistics.quantiles(run.online_s, n=20)[18] * 1e3
