"""A counter's increase over the window, per second of the window."""


def read(spec, obs):
    if spec["counter"] not in obs.counters or obs.window_s <= 0:
        return None
    return obs.counters[spec["counter"]] / obs.window_s
