"""Share of the window the program's own host ledger booked to a bucket.
HOST wall time: never a device share."""


def read(spec, obs):
    if spec["bucket"] not in obs.ledger or obs.window_s <= 0:
        return None
    return obs.ledger[spec["bucket"]] / obs.window_s * spec.get("scale", 1.0)
