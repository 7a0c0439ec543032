"""setup_s: seconds from the process's start to the window's start —
making the data, loading or compiling every program, warming up."""


def read(run):
    return run.setup_s
