"""setup_s: process start to the first request of the traffic (host
clock): imports, building the engine and its weights, and warming up every
shape the traffic reaches, compilation or cache loads included."""


def read(run):
    return run.setup_s
