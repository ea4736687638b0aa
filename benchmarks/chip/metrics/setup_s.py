"""setup_s: seconds from process start to the opening of the window
(imports, data generation, loading or compiling every program, warm-up)."""


def read(run):
    return run.setup_s
