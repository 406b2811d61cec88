"""A percentile over finished requests of the difference of two of the
engine's own timestamps, in milliseconds.
params: ``later``, ``earlier`` (keys of a request: arrival, admitted,
first_token), ``q``."""
from benchmarks import stats


def read(params, obs):
    reqs = obs.get("requests")
    if not reqs:
        return None
    vals = [max(r[params["later"]] - r[params["earlier"]], 0.0) for r in reqs]
    return stats.percentile(vals, params["q"]) * 1e3
