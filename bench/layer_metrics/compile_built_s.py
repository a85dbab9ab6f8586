"""Kernels: the seconds XLA spent building programs in this process (set-up
and warm-up; the window builds none), `built_seconds` of the program's
compile listener (`ydb_tpu.obs.tracing.compile_counts`), beside
`programs_built`: 0 on a warm cache, the other large term of a first
set-up on a cold one."""


def read(run):
    try:
        from ydb_tpu.obs import tracing

        return float(tracing.compile_counts()["built_seconds"])
    except (ImportError, AttributeError, KeyError):
        return None
