"""Process start to the first tick of load: weights, adapters, engine,
warm-up and compilation (the pre-roll is not counted)."""


def read(run, name):
    return run.setup_s
