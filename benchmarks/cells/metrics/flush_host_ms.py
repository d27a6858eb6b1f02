"""Host time to issue one flush epoch, in ms: the mean duration of the
program's `cms.flush_epoch` spans in the window (its device time is
`flush_device_ms`)."""
import program_spans


def read(tr):
    return program_spans.mean_ms(program_spans.named(tr, "flush_epoch"))
