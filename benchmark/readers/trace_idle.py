"""Share (%) of the traced window in which no operation ran on the device,
averaged over the chips used."""


def read(rec):
    red = rec.get("trace")
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
