"""prune_rate (%): (query, block) cells the pruned executor dropped before
their last term chunk, over the cells it considered, from the program's
serving counters over the run."""


def read(run):
    c = run.counters
    if not c.prune_considered:
        return None
    return 100.0 * c.pruned_blocks / c.prune_considered
