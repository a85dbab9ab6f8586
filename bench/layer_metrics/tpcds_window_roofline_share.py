"""Kernels (kernels.window_rank) under a ranking window: the least time
the chip could take over the window stage's time, in %. The least bytes
are the program's process counter `component=window` `bytes_least` (the
partition and order keys read once and the window's column written
once, data and a validity byte) over its `windows`, one execution's, at
the peak HBM bandwidth (peaks.json); the time is the mean
`stages["dq_window"]` of the window's statements: host seconds, so a
reading can only be low. `tpcds_rollup_roofline_share`'s reader, over
the window's counters and key."""

import importlib.util
import pathlib

_path = pathlib.Path(__file__).with_name("tpcds_rollup_roofline_share.py")
_spec = importlib.util.spec_from_file_location("bench_rollup_share", _path)
_rollup = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_rollup)


def read(run):
    return _rollup.least_share("window", "windows", "dq_window", run)
