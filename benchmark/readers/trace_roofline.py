"""A kernel's share of its roofline, in %: the least time the chip could
take for the bytes (or operations) the traced steps needed, over the device
time of the operations matching ``match``. ``bound`` says which peak."""

from benchmark import xplane


def read(spec, obs):
    t = obs.trace
    need = obs.values.get(spec["need"])
    if not t or need is None or obs.peaks is None:
        return None
    s = xplane.matching_seconds(t["op_seconds"], spec["match"])
    if s <= 0:
        return None
    peak = obs.peaks[
        "bytes_per_s" if spec["bound"] == "bytes" else "flops_per_s"
    ]
    return 100.0 * (need / peak) / s
