"""Process start to window start, in seconds."""


def read(spec, obs):
    return obs.setup_s
