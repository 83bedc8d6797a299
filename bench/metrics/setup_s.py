"""From the process's start to the first timed step: imports, device
start-up, weights, the engine, compiles or cache loads, warm-up."""


def read(run):
    return run.setup_s
