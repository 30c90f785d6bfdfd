"""Process start to the first due request: start-up, weights, engine
builds (compiles or compile-cache loads) and the warm-up."""


def read(obs):
    return obs.setup_s
