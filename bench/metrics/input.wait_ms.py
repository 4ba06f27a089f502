"""Mean milliseconds per step that the loop waited for the next batch from
the program's Prefetcher (host clock, the untraced window)."""
import statistics


def read(run):
    return statistics.mean(run.waits) * 1e3
