"""The key-routed deployment: one `CountService(mesh=...)` whose planes hold
their tables sharded by key over a 1-D mesh of the configuration's chips,
where there are that many devices, and over the devices there are
otherwise (the CPU tests' one device)."""
import numpy as np

import harness


def build(cfg, seed, counter):
    import jax
    from jax.sharding import AxisType, Mesh

    from repro.core.sketch import SketchSpec
    from repro.stream import CountService

    devs = jax.devices()
    devs = devs[:min(int(cfg["mesh"]["chips"]), len(devs))]
    mesh = Mesh(np.asarray(devs), (cfg["mesh"]["axis"],),
                axis_types=(AxisType.Auto,))
    svc = CountService(queue_capacity=int(cfg["queue_capacity"]),
                       seed=int(seed), track_top=cfg["track_top"],
                       probe=None, mesh=mesh)
    names = []
    for plane in cfg["planes"]:
        spec = SketchSpec(width=int(plane["width"]),
                          depth=int(plane["depth"]),
                          counter=harness.counter_spec(counter
                                                       or plane["counter"]),
                          seed=int(cfg["sketch_seed"]),
                          packed=bool(cfg["packed"]))
        for n in harness.tenant_names(plane):
            svc.add_tenant(n, spec=spec)
            names.append(n)
    return svc, names
