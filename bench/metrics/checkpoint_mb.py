"""Mean size of one search checkpoint written in the window, in MB (1e6
bytes): the program's ``search_checkpoint_bytes`` histogram, one
observation a checkpoint of ``arrays.npz`` plus ``manifest.json``.  None
where the program has no such histogram or wrote no checkpoint."""
from bench import program


def read(run):
    h = program.hist(run, "search_checkpoint_bytes")
    return None if h is None else h["sum"] / h["count"] / 1e6
