"""Device time of one key-routed flush epoch, in ms: the device time of the
`_routed_epoch` programs (dedup, exchange, in-place update and re-score,
heap re-selection in one `shard_map` program), summed over the chips, over
the chips and over the epochs: the mean time a chip spends in one epoch.
Each chip's run of the program counts once."""
PATTERNS = (r"routed_epoch",)


def read(tr):
    runs = tr.program_runs(PATTERNS)
    if runs == 0:
        return None
    return tr.program_time_s(PATTERNS) / runs * 1e3
