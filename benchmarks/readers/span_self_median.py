"""Median self time of the program's spans of one name, in milliseconds: a
span's duration less the part of it that spans of the ``children`` names
cover (clipped to it, overlaps counted once). Children are found by time,
not by parent id: the kinds export name, start and end.
params: ``span``, ``children`` (a list of span names)."""
import bisect

from benchmarks import stats, trace_reduce


def read(params, obs):
    closed = [s for s in obs.get("spans") or [] if s["end"] is not None]
    parents = [(s["start"], s["end"]) for s in closed
               if s["name"] == params["span"]]
    if not parents:
        return None
    cover = trace_reduce.union((s["start"], s["end"]) for s in closed
                               if s["name"] in params["children"])
    ends = [e for _, e in cover]
    selfs = []
    for lo, hi in parents:
        inside, i = 0.0, bisect.bisect_right(ends, lo)
        while i < len(cover) and cover[i][0] < hi:
            inside += min(cover[i][1], hi) - max(cover[i][0], lo)
            i += 1
        selfs.append(max(hi - lo - inside, 0.0))
    return stats.median(selfs) * 1e3
