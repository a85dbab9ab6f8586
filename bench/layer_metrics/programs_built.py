"""Kernels: programs XLA built in this process (set-up and warm-up; the
window builds none or the run is not `correct`), from the program's one
compile listener (`ydb_tpu.obs.tracing.compile_counts`), which tells a
program built from one fetched out of the persistent cache. 0 on a warm
cache. A program without that counter has nothing to read here."""


def read(run):
    try:
        from ydb_tpu.obs import tracing

        return float(tracing.compile_counts()["built"])
    except (ImportError, AttributeError):
        return None
