"""setup.compile_s: seconds of the set-up segments, which compile the round
program and warm every shape the window uses, by the harness's clock."""


def read(run):
    return run.setup["compile_s"]
