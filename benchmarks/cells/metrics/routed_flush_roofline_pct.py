"""Share of the HBM roofline that the key-routed flush epochs reach, in %.

The least bytes are those of `flush_roofline_pct`, the work itself whatever
implements it: 4 bytes per event landed and, for each distinct key of each
tenant, one cell per row read and written (4 N + 2 c d U).  Routing adds no
bytes the work needs, so they are read alike on one chip and on a mesh.
Time is the `_routed_epoch` programs' device time summed over the chips
(`routed_flush_device_ms`'s), against one chip's 819 GB/s: every chip's
time counts, so the share cannot pass 100%."""
import importlib.util
import pathlib


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, pathlib.Path(__file__).with_name(f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_fr = _load("flush_roofline_pct")
_rd = _load("routed_flush_device_ms")


def read(tr):
    epochs = tr.ctx.get("epochs")
    if not epochs:
        return None
    t = tr.program_time_s(_rd.PATTERNS)
    if t <= 0:
        return None
    least = sum(_fr.least_bytes(e) for e in epochs)
    return least / tr.peaks["hbm_bytes_per_s"] / t * 100
