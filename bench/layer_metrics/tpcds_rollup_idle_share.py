"""Device: 1 - the union of device-operation intervals over the traced
window, in %: `device_idle_share`'s reader, listed for the cell that
runs TPC-DS q67. What is idle there is the host between the stages (the
rollup's level rows read back, the channels' row counts, the answer
copied out)."""

import importlib.util
import pathlib

_path = pathlib.Path(__file__).with_name("device_idle_share.py")
_spec = importlib.util.spec_from_file_location("bench_device_idle", _path)
_idle = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_idle)

read = _idle.read
