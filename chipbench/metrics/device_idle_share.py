"""Share of the traced window in which no operation ran on the device,
averaged over the devices (percent).  Layer: tick on device."""


def read(run):
    devs = run.trace["devices"]
    if not devs:
        return None
    return 100.0 * sum(d["idle_share"] for d in devs.values()) / len(devs)
