"""Share of the key-routed flush epoch's device time spent exchanging keys,
in %: the device time of the ops of the `_routed_epoch` programs whose
names match `all-to-all` over that of all their ops, both summed over the
chips."""
import importlib.util
import pathlib
import re

_spec = importlib.util.spec_from_file_location(
    "routed_flush_device_ms",
    pathlib.Path(__file__).with_name("routed_flush_device_ms.py"))
_rd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_rd)

EXCHANGE = r"all-to-all"


def read(tr):
    epoch = tr.program_time_s(_rd.PATTERNS)
    if epoch <= 0:
        return None
    ops = [o for o in tr.ops if re.search(EXCHANGE, o[3])
           and any(re.search(p, o[4]) for p in _rd.PATTERNS)]
    return tr._union_s(ops) / epoch * 100
