"""Plan + executor choice (plan/executor.py): device program executions
in the traced window over the statements completed in it."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["statements"] or not trace["launches"]:
        return None
    return trace["launches"] / trace["statements"]
