"""Share of the window in which a cross-chip collective (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) ran on a
chip, averaged over the chips, from the device trace."""


def read(run):
    t = run["trace"]
    return 100.0 * t["collective_s"] / t["window_s"]
