"""Exchange spool: leaf tasks answered from spooled output during the
window (`scheduler.stats["spool_hits"]`). Reads 0 while no statement
shares a leaf fragment's text with an earlier one; anything else means
that part of the window measured the spool (q3's customer build
fragment, once a SEGMENT comes round again)."""


def read(run):
    return run["after"]["spool_hits"] - run["before"]["spool_hits"]
