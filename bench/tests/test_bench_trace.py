"""The trace -> metric reduction on hand-built event lists with known
answers (times in ns)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import trace  # noqa: E402

# three device operations, two of them overlapping: busy [0,40) u [50,80)
OPS = [("fusion.1", 0, 30), ("_paged_body_fused", 10, 30),
       ("_prefill_body_fused", 50, 30)]
# two program executions: the first holds the decode kernel
MODULES = [("jit__lambda", 0, 45), ("jit__lambda", 48, 40)]


def test_union_busy_and_gaps():
    assert trace.union(OPS) == [(0, 40), (50, 80)]
    assert trace.busy_ns(OPS) == 70
    assert trace.gaps(OPS) == [(40, 50)]
    assert trace.gaps(OPS, lo=-5, hi=100) == [(-5, 0), (40, 50), (80, 100)]
    assert trace.union([("a", 5, 0)]) == []


def test_kernel_time_and_containing_programs():
    assert trace.time_ns(OPS, r"_paged(_partial)?_body") == 30
    assert trace.time_ns(OPS, r"_prefill_body") == 30
    assert trace.containing(MODULES, OPS, r"_paged_body") == [MODULES[0]]
    assert trace.containing(MODULES, OPS, r"_prefill_body") == [MODULES[1]]
    assert trace.containing(MODULES, OPS, r"nothing") == []


def test_top_sums_by_name():
    evs = [("a", 0, 5), ("b", 0, 7), ("a", 10, 5), ("c", 0, 1)]
    assert trace.top(evs, 2) == [["a", 10e-9], ["b", 7e-9]]


def test_gaps_go_to_the_innermost_host_event():
    # host: run_paged [0,100) holds argmax [38,52) and table [70,95)
    host = [("run_paged", 0, 100), ("argmax", 38, 14), ("table", 70, 25)]
    idle = [(40, 50), (80, 90), (120, 130)]
    got = trace.attribute(idle, host)
    assert got == [["(no host event)", 10e-9], ["argmax", 10e-9],
                   ["table", 10e-9]]
    # a gap inside run_paged but outside both children
    assert trace.attribute([(60, 64)], host) == [["run_paged", 4e-9]]
