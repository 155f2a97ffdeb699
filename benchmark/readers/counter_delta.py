"""A counter's increase over the window, as a count."""


def read(spec, obs):
    return obs.counters.get(spec["counter"])
