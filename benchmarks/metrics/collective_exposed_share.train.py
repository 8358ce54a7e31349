"""Time a collective holds a device's operation lane while no compute runs
there, over the traced stretch; the mean over devices."""

UNIT, BETTER, SOURCE = "%", "lower", "device_trace"
LAYER = "ZeRO partitioning (runtime/zero/)"
MOVES = "train_tokens_per_s"


def read(record):
    reduced = record.get("reduced")
    if not reduced or reduced.get("collective_exposed_share") is None:
        return None
    return 100.0 * reduced["collective_exposed_share"]
