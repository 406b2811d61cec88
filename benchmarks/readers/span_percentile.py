"""A percentile of the durations of the program's spans of one name, in
milliseconds. params: ``span``, ``q``."""
from benchmarks import stats


def read(params, obs):
    durs = [s["end"] - s["start"] for s in obs.get("spans") or []
            if s["name"] == params["span"] and s["end"] is not None]
    return stats.percentile(durs, params["q"]) * 1e3 if durs else None
