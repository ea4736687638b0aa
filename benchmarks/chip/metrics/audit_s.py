"""audit_s: window wall time over the embedding-table audits completed in
it; one audit is FastVAT(metric="cosine", ...).fit(X) then .assess(),
report on the host."""


def read(run):
    if not run.completed_in_window:
        return None
    return run.window_s / run.completed_in_window
