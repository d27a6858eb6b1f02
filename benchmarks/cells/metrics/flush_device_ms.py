"""Device time of one flush epoch, in ms: the epoch's programs, matched by
their jit names, over the number of epochs in the traced window.

An epoch of a tracked plane is `flush_rows_inputs` (queue gather), the
fused update + candidate re-score `_update_score_rows_xla_jit`, and the
heap re-selection `_select_stacked`; the epochs are counted by the update
program's runs.
"""
EPOCH = (r"update_score_rows",)
PATTERNS = (r"update_score_rows", r"flush_rows_inputs", r"select_stacked")


def read(tr):
    n = tr.program_runs(EPOCH)
    if n == 0:
        return None
    return tr.program_time_s(PATTERNS) / n * 1e3
