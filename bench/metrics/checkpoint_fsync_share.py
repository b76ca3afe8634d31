"""Share of the window spent in the fsyncs of checkpoint writes
(``checkpoint_fsync_seconds``: data, manifest and directories)."""
from bench import program


def read(run):
    return program.window_share(run, "checkpoint_fsync_seconds")
