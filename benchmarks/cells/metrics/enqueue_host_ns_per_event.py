"""Host time of the service's enqueue path per event, in ns: the harness's
wall clock around each `enqueue_many` call (which only dispatches; it
blocks where the service's own queue-pressure flush or JAX's dispatch
queue makes it), over the events it carried."""


def read(tr):
    n = tr.ctx.get("enqueue_events")
    if not n:
        return None
    return tr.ctx["enqueue_s"] / n * 1e9
