"""``executor.deferred_read_share`` on a window written out span by span:
the share of decode tokens read one step late, and None where the program
reads no token late."""
import pytest

from chipbench.harness.catalog import reader
from chipbench.tests.conftest import ROOT
from chipbench.tests.test_chipbench_spans import _reduced, _run

NAME = "executor.deferred_read_share"

# The window opens at 100 s and closes at 110 s on the trace's clock.  In
# it, rid 1 decodes four steps and is preempted (the checkpoint's read
# waits), resumes for two more, the last read in its own step: four late
# reads and two that wait.  One late read before the open and one waiting
# read after the close are left out.
HOST = [
    ("executor.read_deferred", 99.0, 99.01),
    ("chipbench.open", 100.0, 100.0),
    ("chipbench.dispatch:1", 100.1, 100.1),
    ("executor.decode", 100.2, 100.3),
    ("executor.decode", 100.4, 100.5),
    ("executor.read_deferred", 100.45, 100.46),
    ("executor.decode", 100.6, 100.7),
    ("executor.read_deferred", 100.65, 100.66),
    ("executor.decode", 100.8, 100.9),
    ("executor.read_deferred", 100.85, 100.86),
    ("executor.checkpoint", 101.0, 101.1),
    ("executor.sync", 101.0, 101.05),
    ("chipbench.dispatch:1", 102.0, 102.0),
    ("executor.decode", 102.1, 102.2),
    ("executor.decode", 102.3, 102.4),
    ("executor.read_deferred", 102.35, 102.36),
    ("executor.sync", 102.37, 102.38),
    ("executor.sync", 110.5, 110.6),
]


def test_share_of_tokens_read_late_in_the_window():
    assert reader(ROOT, NAME)(_run(_reduced(HOST))) == pytest.approx(
        100.0 * 4 / 6)


def test_every_read_late_reads_100():
    host = [h for h in HOST if h[0] != "executor.sync"]
    assert reader(ROOT, NAME)(_run(_reduced(host))) == pytest.approx(100.0)


@pytest.mark.parametrize("left_out", [
    "no trace", "neither span", "no late read", "none in the window"])
def test_none_without_a_late_read(left_out):
    host = {
        "neither span": [h for h in HOST if not h[0].startswith(
            ("executor.read_deferred", "executor.sync"))],
        # a program that reads every token in its own step
        "no late read": [h for h in HOST if h[0] != "executor.read_deferred"],
        "none in the window": [h for h in HOST
                               if h[0].startswith("chipbench.")
                               or not 100.0 <= h[1] < 110.0],
    }.get(left_out)
    red = None if host is None else _reduced(host)
    assert reader(ROOT, NAME)(_run(red)) is None
