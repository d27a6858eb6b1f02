"""Host time of the service's batched enqueue per event, in ns, from the
program's own spans: the calling thread's CPU time (`cpu_ns`) in the
outermost `cms.enqueue_many` spans of the window, less that of the
`cms.flush_epoch` spans nested in them, over the events they carried
(their `events` count).  The in-program twin of
`enqueue_host_ns_per_event` with the flush epoch's host work and the time
the host spends blocked (on the device or otherwise) taken out."""
import program_spans


def read(tr):
    calls = program_spans.outer(tr, "enqueue_many")
    events = sum(c.stats.get("events", 0) for c in calls)
    epochs = [e for e in program_spans.named(tr, "flush_epoch")
              if any(c.holds(e) for c in calls)]
    if not events or any("cpu_ns" not in s.stats for s in calls + epochs):
        return None
    cpu = (sum(c.stats["cpu_ns"] for c in calls)
           - sum(e.stats["cpu_ns"] for e in epochs))
    return cpu / events
