"""setup.task_s: seconds to build the experiment (data, split, initial
weights, channel draw, Algorithm 1), by the harness's clock."""


def read(run):
    return run.setup["task_s"]
