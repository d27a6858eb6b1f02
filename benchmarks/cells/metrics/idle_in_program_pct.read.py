"""`idle_in_program_pct.ingest`'s reader, for the cells that report
`read_p95_ms`."""
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "idle_in_program_pct_ingest",
    pathlib.Path(__file__).with_name("idle_in_program_pct.ingest.py"))
_m = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_m)

read = _m.read
