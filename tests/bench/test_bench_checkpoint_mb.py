"""``checkpoint_mb``: the window mean of the program's
``search_checkpoint_bytes`` histogram, in MB, and None where the program
has no such histogram or wrote no checkpoint in the window."""
import pytest

from bench import run as brun


def _run(hists):
    registry = {"counters": {}, "histograms": {
        (name, ()): dict(sum=s, count=n) for name, s, n in hists}}
    win = brun.Window(t0=0.0, t1=10.0, attempted=1, failed=0, e2e={})
    return brun.Run(window=win, compiles=(0, 0.0, 0), registry=registry,
                    trace=None, peaks={})


def _read(run):
    return brun.reader("checkpoint_mb")(run)


@pytest.mark.parametrize("total, n, want", [(92e6, 4, 23.0),
                                            (4.5e6, 1, 4.5)])
def test_reads_the_window_mean_in_mb(total, n, want):
    hists = [("search_checkpoint_bytes", total, n),
             ("checkpoint_fsync_seconds", 0.25, n)]
    assert _read(_run(hists)) == pytest.approx(want)


@pytest.mark.parametrize("hists", [
    [],                                            # the parent's program
    [("checkpoint_fsync_seconds", 0.25, 4)],       # only other instruments
    [("search_checkpoint_bytes", 0.0, 0)],         # no checkpoint in window
])
def test_reads_nothing_without_a_checkpoint_histogram(hists):
    assert _read(_run(hists)) is None
