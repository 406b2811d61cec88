"""``BENCHMARK.json`` as accepted, and again with ONE CELL APPENDED, as the
next PR that brings a configuration will leave it: one entry more at the end
of ``configs``, one at the end of ``workloads``, the new cell's name at the
end of every metric's ``workloads`` that lists the donor cell.

Built in memory from files that are there: the appended configuration is the
donor's under a second name (its ``file`` is the donor's, which only a
rehearsal may do), its mix the donor's. Every test under
``tests/unit/benchmarks`` that reads ``BENCHMARK.json`` takes the ``bench``
fixture (conftest.py) or ``CELL_CASES`` and so runs under both: a test that
pins the end of a list, a list's length or the absence of a neighbour fails
under the second. A later family's test does the same (benchmarks/README.md).
"""
import copy

import pytest

from benchmarks import harness

DONOR = "k-exaone-236b-a23b.serve-mixed-lengths"
APPENDED_CONFIG = "rehearsal-appended"


def one_cell_appended(bench: dict, donor: str = DONOR,
                      config: str = APPENDED_CONFIG) -> dict:
    """A deep copy of ``bench`` with the donor cell's twin appended last."""
    out = copy.deepcopy(bench)
    cell = next(w for w in out["workloads"] if w["name"] == donor)
    entry = next(c for c in out["configs"] if c["name"] == cell["config"])
    name = f"{config}.{cell['traffic']}"
    out["configs"].append(dict(entry, name=config))
    out["workloads"].append(dict(cell, name=name, config=config))
    for group in ("end_to_end", "per_layer"):
        for m in out[group]:
            if donor in m.get("workloads", ()):
                m["workloads"].append(name)
    return out


ACCEPTED = harness.benchmark_json()
BENCHES = {"accepted": ACCEPTED, "one-appended": one_cell_appended(ACCEPTED)}
APPENDED_CELL = BENCHES["one-appended"]["workloads"][-1]["name"]
# (bench, cell) for every cell of both: what a test of every cell runs over
CELL_CASES = [pytest.param(b, w["name"], id=f"{variant}-{w['name']}")
              for variant, b in BENCHES.items() for w in b["workloads"]]
