"""Host time the service spends issuing one read, in ms: the mean duration
of the program's outermost `cms.query` spans in the window (the harness's
wait for the answer on the host is outside them)."""
import program_spans


def read(tr):
    return program_spans.mean_ms(program_spans.outer(tr, "query"))
