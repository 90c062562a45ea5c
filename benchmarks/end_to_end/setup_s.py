"""Process start to window start: imports, device, weights, server, every
compilation or cache load, warm-up."""


def read(obs):
    return obs.setup_s
