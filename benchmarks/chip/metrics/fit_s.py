"""fit_s: window wall time over the exact assessments completed in it;
one assessment is FastVAT(...).fit(X) then .assess(), report on the
host."""


def read(run):
    if not run.completed_in_window:
        return None
    return run.window_s / run.completed_in_window
